"""`loss_ms.train` (PR 26): the device time whose root scope is
`cross_entropy`, read off the three steps cut from a chip trace of the
program as PR 25 had it (float32 `log_softmax` written out between the head's
matmuls), and nothing where there is nothing to read."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import harness, program_trace as pt, trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
pytestmark = pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")


def read(m):
    return harness.load_reader("layer_metrics", "loss_ms.train")(m)


def test_recorded_trace_reads_the_parents_loss_time():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "gpt3-1p3b_1chip_3steps_spans.textproto")) as f:
        trace = pt.load(ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(DATA, "gpt3-1p3b_1chip_step_scopes.json")) as f:
        scopes = {k: tuple(v) for k, v in json.load(f).items()}
    (program,) = trace["programs"]
    reduced = pt.reduce(dict(trace, programs={program: scopes}), expected=tr.reduce(trace))
    m = {"run": {"trace": {"steps": 3}}, "program_trace": reduced}
    # my chip run, PR 25, seed 2501: 1.17 ms of it `subtract_subtract_fusion`
    assert read(m) == pytest.approx(1.8377446666666666, rel=1e-9)
    assert scopes["subtract_subtract_fusion"] == ("cross_entropy",)


@pytest.mark.parametrize("m", [
    {"run": {"trace": None}},                                  # an untraced run
    {"run": {"trace": {"steps": 2}}, "program_trace": None},   # a program without the spans
    {"run": {"trace": {"steps": 2}},                           # a cache another tree filled
     "program_trace": {"scope_ms": None, "held_ms": None}},
])
def test_reads_nothing_where_there_is_nothing_to_read(m):
    assert read(m) is None


def test_a_step_with_no_loss_operation_of_its_own_reads_zero():
    m = {"run": {"trace": {"steps": 2}},
         "program_trace": {"scope_ms": {"linear": 3.0}, "held_ms": {"linear": 3.0}}}
    assert read(m) == 0.0


def test_the_manifest_names_it_last_and_as_the_other_scope_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # found by name: it was the last when PR 26 added it, and every later PR
    # appends after it, so no position is pinned
    by_name = {metric["name"]: metric for metric in per_layer}
    assert len(by_name) == len(per_layer)
    assert {k: v for k, v in by_name["loss_ms.train"].items() if k != "name"} \
        == {k: v for k, v in by_name["attention_ms.train"].items() if k != "name"}
