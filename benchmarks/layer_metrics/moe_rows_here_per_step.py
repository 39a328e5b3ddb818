"""(token, expert) pairs routed to the experts held here, per step, summed
over the expert layers: the device counter `moe.rows_here_total` over the
steps run since the model was built, the compared and settling steps among
them (benchmarks/lfm2_readings.py). Uniform routing gives tokens x experts
per token x held / published a layer; no pair is ever dropped, so these are
also the rows of the grouped products."""
from benchmarks import lfm2_readings


def read(m):
    routed = lfm2_readings.routing(m)
    return None if routed is None else routed["rows_per_step"]
