#!/usr/bin/env python
"""Lint: every always-on metric name follows ``subsystem.noun_unit``.

The metrics registry (paddle_tpu/profiler/metrics.py) accepts any string, so
nothing stops ``serving.latency`` today and ``serving.request_latency_ms``
tomorrow from coexisting as two dashboards' worth of orphaned series. The
check itself now lives in the unified analysis framework
(paddle_tpu/analysis/passes/metric_names.py, run with the rest of the
passes by ``tools/lint.py``); this shim keeps the standalone CLI, its exit
codes, and — deliberately — the manifests: ``SUBSYSTEMS`` / ``UNITS`` /
``GRANDFATHERED`` stay as plain literals HERE because tests/test_lints.py
ast-parses them to guard the naming contract, and this file remains where
a new subsystem is registered (a one-line reviewed diff).

Dynamic segments (f-string fields, %-format specs) are normalized to ``{}``
and allowed inside the noun — ``steptime.rank{}_ms`` is one metric family.
Names whose first argument is a bare variable cannot be extracted and are
skipped; the convention is enforced where names are minted, i.e. at literal
call sites. Pre-existing names that predate the convention are pinned in
``GRANDFATHERED`` (renaming them would break recorded artifacts and the
integrity test assertions) — do not add new entries.

Run directly or via tests/test_lints.py / tests/test_observability.py.
"""
from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Directories/files scanned (relative to repo root).
SCAN = ["paddle_tpu", "bench.py"]

# Registered metric subsystems (the manifest). A new prefix fails the lint
# until it is added here — the review of that one-line diff is the naming
# review.
SUBSYSTEMS = [
    "attention",     # the path scaled_dot_product_attention took (ops/attention.py)
    "campaign",      # chaos-campaign engine (resilience/campaign.py)
    "ckpt",          # zero-stall checkpointing (resilience/snapshot.py)
    "compile",       # every compile request of the process, by set-up phase
                     # (profiler/compile_events.py)
    "compiled_step", # whole-step compilation (jit/compiled_step.py)
    "decode",        # continuous-batching decode (serving/decode/)
    "dispatch",      # the op dispatch seam (core/dispatch.py)
    "disagg",        # disaggregated prefill/decode (serving/disagg.py)
    "dsa",           # the sparse-attention index: pairs selected, tiles
                     # skipped (text/models/keye_vl2.py, ops/sparse_index.py)
    "integrity",     # SDC defense (checksum consensus, replay)
    "io",            # input pipeline / data workers
    "kda",           # Kimi Delta Attention's chunked op (ops/kda.py)
    "metrics",       # the registry/exporter's own health
    "moe",           # expert layers: elastic expert parallelism
                     # (fleet/expert_parallel.py), routing load (incubate/moe.py)
    "prefix",        # prefix-sharing KV cache (serving/decode/prefix.py)
    "profiler",      # profiler-internal (samples/sec, ...)
    "rollout",       # live model rollout (serving/rollout.py)
    "runtime",       # the process's own set-up: the package's import, the
                     # set-up timeline's bound (profiler/compile_events.py)
    "serving",       # inference server
    "short_conv",    # the path F.short_conv_silu took (nn/functional/conv.py)
    "slo",           # SLO burn-rate accounting (serving/metrics.py)
    "spec",          # speculative decoding (serving/decode/specdecode.py)
    "steptime",      # per-rank step-time health beacons
    "steptimer",     # phase attribution (docs/observability.md)
    "straggler",     # straggler-quarantine ratios
    "to_static",     # compiled-step launches and compiles (jit/to_static.py)
    "trace",         # request tracer health (profiler/tracing.py)
]

# Unit suffixes a metric name must end with (after stripping ``{}`` fields).
UNITS = ["bytes", "count", "ms", "per_sec", "ratio", "sec", "total", "us"]

# Names minted before this convention existed. Renaming them would orphan
# recorded BENCH/flight artifacts and break assertions in
# tests/test_integrity, so they are pinned, not fixed. FROZEN: new names
# must pass the pattern instead.
GRANDFATHERED = [
    "straggler.rank{}",     # value is a ratio; name predates unit suffixes
    "{}.{}",                # serving export_to_profiler re-emits snapshot
                            # keys under a caller prefix; the source names
                            # are validated at their minting sites above
]

# Calls whose first argument mints a metric name. ``observe_many`` takes
# (name, value) pairs instead and is handled separately.
NAME_CALLS = {"record_counter", "record_sample",
              "inc_counter", "set_gauge", "observe", "register_gauge_fn",
              "register_counter_fn"}
PAIRS_CALLS = {"observe_many"}
# Of those, the registry methods are only linted when the receiver is
# recognizably the metrics registry (get_registry(), self._registry, ...):
# ``observe`` is far too common a method name to lint unconditionally.
REGISTRY_ONLY = {"inc_counter", "set_gauge", "observe", "register_gauge_fn",
                 "register_counter_fn", "observe_many"}


def _analysis():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from lint import load_analysis
    finally:
        sys.path.pop(0)
    return load_analysis(REPO)


def check(repo=REPO):
    """Legacy API: ([problems], names_checked) (framework-backed)."""
    analysis = _analysis()
    ctx = analysis.AnalysisContext(repo)
    p = analysis.get_pass("metric-names")()
    findings = p.run(ctx)
    return [f.message for f in findings], p.templates_checked


def main():
    problems, checked = check()
    if problems:
        print("metric-name lint FAILED:")
        for p in problems:
            print("  -", p)
        return 1
    print(f"metric-name lint OK ({checked} name templates checked, "
          f"{len(SUBSYSTEMS)} subsystems registered)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
