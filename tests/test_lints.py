"""Tier-1 smoke for the repo's own lints/gates (tools/).

Running these here means a PR that breaks a checker — or removes a
fault-injection hook the chaos suite depends on — fails the normal test
run, not just a CI step somebody has to remember to wire up.
"""
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(*argv, env=None):
    import os
    full_env = {**os.environ, **env} if env else None
    return subprocess.run([sys.executable, *map(str, argv)], cwd=REPO,
                          capture_output=True, text=True, timeout=120,
                          env=full_env)


def _pass_literal(module_name, var_name):
    """Parse a manifest literal (SEEDED/PAIRS/CONTRACTED) out of a pass
    module's source — source-level on purpose, so the guard holds even
    if the module under test is broken enough not to import."""
    import ast
    src = (REPO / "paddle_tpu" / "analysis" / "passes"
           / f"{module_name}.py").read_text()
    tree = ast.parse(src)
    node = next(
        n.value for n in ast.walk(tree)
        if isinstance(n, ast.Assign)
        and any(getattr(t, "id", None) == var_name for t in n.targets))
    return ast.literal_eval(node)


LINT_PASSES = ("lock-discipline", "blocking-call", "typed-error",
               "flag-hygiene", "injection-points", "metric-names",
               "span-names", "donation-taint", "jit-hygiene", "host-sync",
               "resource-lifecycle")


def test_paddle_lint_clean():
    """The tier-1 gate (docs/static_analysis.md): the full paddle-lint
    run — every registered pass over the whole tree — must be clean with
    the shipped (empty) waiver baseline."""
    r = _run(REPO / "tools" / "lint.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "paddle-lint OK" in r.stdout
    for name in LINT_PASSES:
        assert f"{name}: 0 finding(s)" in r.stdout, r.stdout


def test_paddle_lint_json_clean():
    import json
    r = _run(REPO / "tools" / "lint.py", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["findings"] == []
    assert set(report["passes"]) == set(LINT_PASSES)


def test_paddle_lint_changed_smoke():
    """--changed restricts reporting to git-dirty files (the fast
    pre-push hook); a dirty-but-clean tree must still exit 0."""
    r = _run(REPO / "tools" / "lint.py", "--changed")
    assert r.returncode == 0, r.stdout + r.stderr


def test_paddle_lint_pass_selection():
    r = _run(REPO / "tools" / "lint.py", "--list")
    assert r.returncode == 0, r.stdout + r.stderr
    for name in LINT_PASSES:
        assert name in r.stdout
    r = _run(REPO / "tools" / "lint.py", "--pass", "no-such-pass")
    assert r.returncode == 2
    assert "unknown pass" in r.stderr


def test_paddle_lint_result_cache_and_stats_budget(tmp_path):
    """The per-file result cache (paddle_tpu/analysis/cache.py) must make
    the warm full run fast: cold run warms the cache under an isolated
    PADDLE_TPU_ARTIFACTS_DIR, the warm run reports cache hits via --stats,
    its reported per-pass total stays under the 5s budget, and the whole
    warm process (interpreter included) finishes in under 2s wall."""
    import time
    env = {"PADDLE_TPU_ARTIFACTS_DIR": str(tmp_path)}
    cold = _run(REPO / "tools" / "lint.py", "--stats", env=env)
    assert cold.returncode == 0, cold.stdout + cold.stderr
    t0 = time.perf_counter()
    warm = _run(REPO / "tools" / "lint.py", "--stats", env=env)
    warm_wall = time.perf_counter() - t0
    assert warm.returncode == 0, warm.stdout + warm.stderr
    assert "(cache hit)" in warm.stdout, warm.stdout
    total_line = next(ln for ln in warm.stdout.splitlines()
                      if "stats: total" in ln)
    total_s = float(total_line.split()[-1].rstrip("s"))
    assert total_s < 5.0, warm.stdout
    assert warm_wall < 2.0, (warm_wall, warm.stdout)


def test_paddle_lint_no_cache_smoke():
    r = _run(REPO / "tools" / "lint.py", "--no-cache",
             "--pass", "typed-error")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "typed-error: 0 finding(s)" in r.stdout


def test_paddle_lint_since_bad_revision_is_usage_error():
    r = _run(REPO / "tools" / "lint.py", "--since",
             "no-such-revision-xyz")
    assert r.returncode == 2
    assert "--since" in r.stderr


def test_donation_taint_manifest_guard():
    """The trace-safety PR's contract: the donation/taint seams stay
    registered and the contracted attribute set stays intact. Guard the
    SEEDED/CONTRACTED manifests so a refactor can't silently disarm the
    direct-write check along with the annotation."""
    seeded = set(_pass_literal("donation_taint", "SEEDED"))
    assert {("paddle_tpu/core/tensor.py", "Tensor._value"),
            ("paddle_tpu/core/tensor.py", "Tensor.set_value"),
            ("paddle_tpu/core/tensor.py", "Tensor._replace_value"),
            ("paddle_tpu/jit/to_static.py", "StaticFunction._run"),
            ("paddle_tpu/serving/decode/kv_cache.py",
             "KVBlockPool.release")} <= seeded
    contracted = set(_pass_literal("donation_taint", "CONTRACTED"))
    assert {"_val", "_donate_unsafe", "_degen_cache"} <= contracted


def test_jit_hygiene_manifest_guard():
    """The two real trace roots — the per-step pure_fn and the K-step
    scan_fn — must stay contracted as '# traced-fn:' bodies."""
    seeded = set(_pass_literal("jit_hygiene", "SEEDED"))
    assert {("paddle_tpu/jit/to_static.py",
             "StaticFunction._make_pure_fn.pure_fn"),
            ("paddle_tpu/jit/to_static.py",
             "StaticFunction._build_scan.scan_fn")} <= seeded


def test_host_sync_manifest_guard():
    """The contracted hot paths (step dispatch, decode tick, serving
    dispatch, prefetch staging) must stay registered with host-sync."""
    seeded = set(_pass_literal("host_sync", "SEEDED"))
    assert {("paddle_tpu/jit/compiled_step.py",
             "CompiledTrainStep.__call__"),
            ("paddle_tpu/jit/compiled_step.py",
             "CompiledTrainStep.run_steps"),
            ("paddle_tpu/serving/decode/compiled_decode.py",
             "CompiledDecodeStep.run"),
            ("paddle_tpu/serving/decode/engine.py", "DecodeEngine.step"),
            ("paddle_tpu/serving/scheduler.py", "Scheduler.dispatch"),
            ("paddle_tpu/hapi/prefetch.py",
             "InputPrefetcher._stage")} <= seeded


def test_resource_lifecycle_manifest_guard():
    """The acquire/release pairs — KV blocks, dtensor table entries,
    flight-recorder ring entries, replica admission — stay contracted."""
    pairs = {(acq, rels): (prefix, recv, mode)
             for prefix, acq, rels, recv, mode
             in _pass_literal("resource_lifecycle", "PAIRS")}
    assert ("try_allocate", ("release",)) in pairs
    # prefix-sharing PR: every pool.ref must meet a pool.unref (or the
    # release alias) on all paths — the refcount layer under the radix cache
    prefix, recv, mode = pairs[("ref", ("unref", "release"))]
    assert "pool" in recv and mode == "strict"
    prefix, recv, mode = pairs[("start", ("finish",))]
    assert "recorder" in recv and mode == "strict"
    prefix, recv, mode = pairs[
        ("add_replica", ("remove_replica", "begin_drain"))]
    assert mode == "admit"


def test_tracesan_loads_under_lint_alias_without_jax():
    """tracesan must stay importable in the linter process (the alias
    loader, no jax): its heavy imports are deferred to enable()."""
    code = (
        "import sys; sys.path.insert(0, 'tools')\n"
        "from lint import load_analysis\n"
        "m = load_analysis()\n"
        "import importlib\n"
        "ts = importlib.import_module('_paddle_lint.tracesan')\n"
        "assert hasattr(ts, 'tracking') and hasattr(ts, 'enable')\n"
        "assert 'jax' not in sys.modules\n"
        "assert 'paddle_tpu' not in sys.modules\n"
        "print('tracesan-alias-ok')\n")
    r = _run("-c", code)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tracesan-alias-ok" in r.stdout


def test_fault_injection_lint_passes_on_tree():
    r = _run(REPO / "tools" / "check_injection_points.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fault-injection lint OK" in r.stdout


def test_injection_lint_covers_serving_entry_points():
    """The serving PR's contract: enqueue/dispatch/reply must stay
    chaos-testable. Guard the lint MANIFEST itself so a refactor can't
    silently drop the requirement along with the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert "put" in entries[
        ("paddle_tpu/serving/batcher.py", "class:BatchQueue")]
    assert "dispatch" in entries[
        ("paddle_tpu/serving/scheduler.py", "class:Scheduler")]
    assert "_reply" in entries[
        ("paddle_tpu/serving/server.py", "class:InferenceServer")]


def test_injection_lint_covers_recovery_entry_points():
    """The elastic-recovery PR's contract: the rendezvous, the restart
    cycle, and store GC must stay chaos-testable (sites recovery.rendezvous
    / recovery.restart / store.gc). Guard the MANIFEST so a refactor can't
    silently drop the requirement along with the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert "gc_tmp" in entries[
        ("paddle_tpu/distributed/fleet/elastic.py", "class:FileStore")]
    assert "rendezvous" in entries[
        ("paddle_tpu/distributed/fleet/elastic.py", "class:ElasticManager")]
    assert "restart" in entries[
        ("paddle_tpu/resilience/recovery.py", "class:RecoveryManager")]


def test_injection_lint_covers_integrity_entry_points():
    """The hardware-health PR's contract: the preflight KAT, the consensus
    checksum (with its non-raising device.bitflip corruption hook), and the
    step replay must stay chaos-testable. Guard both the MANIFEST and the
    HOOK_CALLS set so a refactor can't silently drop the requirement."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)

    def _assigned(name):
        return next(
            node.value for node in ast.walk(tree)
            if isinstance(node, ast.Assign)
            and any(getattr(t, "id", None) == name for t in node.targets))

    manifest = ast.literal_eval(_assigned("REQUIRED"))
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert "preflight_kat" in entries[
        ("paddle_tpu/resilience/health.py", "module")]
    assert "checksum_state" in entries[
        ("paddle_tpu/resilience/integrity.py", "module")]
    assert "replay" in entries[
        ("paddle_tpu/resilience/integrity.py", "class:StepReplayBuffer")]
    hooks = ast.literal_eval(_assigned("HOOK_CALLS"))
    assert "should_inject" in hooks


def test_injection_lint_covers_checkpoint_entry_points():
    """The zero-stall checkpointing PR's contract: the foreground snapshot,
    the background serialize, every commit file boundary, and retention-GC
    deletes must stay chaos-testable (sites ckpt.snapshot / ckpt.serialize /
    ckpt.commit / fs.remove). Guard the MANIFEST so a refactor can't
    silently drop the requirement along with the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    ck = entries[("paddle_tpu/resilience/snapshot.py",
                  "class:AsyncCheckpointer")]
    assert {"save", "_commit", "_remove"} <= set(ck)
    assert "serialize_file" in entries[
        ("paddle_tpu/resilience/snapshot.py", "module")]
    assert "clean_redundant_epochs" in entries[
        ("paddle_tpu/incubate/checkpoint.py", "class:CheckpointSaver")]


def test_injection_lint_covers_overload_entry_points():
    """The overload-control PR's contract: the hedge boundary
    (serving.hedge, carried by Scheduler._hedge_site) and elastic resizes
    (serving.scale in Autoscaler.scale_up/scale_down) must stay
    chaos-testable, and both dispatch attempts must keep funnelling through
    the hooked _attempt chokepoint. Guard the MANIFEST and HOOK_CALLS so a
    refactor can't silently drop the requirement along with the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)

    def _assigned(name):
        return next(
            node.value for node in ast.walk(tree)
            if isinstance(node, ast.Assign)
            and any(getattr(t, "id", None) == name for t in node.targets))

    manifest = ast.literal_eval(_assigned("REQUIRED"))
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert "_hedge_site" in entries[
        ("paddle_tpu/serving/scheduler.py", "class:Scheduler")]
    assert {"scale_up", "scale_down"} <= set(entries[
        ("paddle_tpu/serving/autoscaler.py", "class:Autoscaler")])
    hooks = ast.literal_eval(_assigned("HOOK_CALLS"))
    assert "_attempt" in hooks


def test_injection_lint_covers_rollout_entry_points():
    """The live-rollout PR's contract: the manifest watch, the weight load,
    the replica swap, and the canary verify must stay chaos-testable (sites
    rollout.watch / rollout.load / rollout.swap / rollout.verify). Guard the
    MANIFEST so a refactor can't silently drop the requirement along with
    the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert "poll" in entries[
        ("paddle_tpu/serving/rollout.py", "class:ManifestWatcher")]
    assert {"_load", "_swap_one", "_verify_canary"} <= set(entries[
        ("paddle_tpu/serving/rollout.py", "class:RolloutController")])


def test_injection_lint_covers_decode_entry_points():
    """The continuous-batching decode PR's contract: the join admission
    (decode.join), the prefill chunk and the decode round (decode.prefill /
    decode.step — replica death mid-either must resolve as a replay), and
    the eviction cleanup (decode.evict) must stay chaos-testable. Guard the
    MANIFEST so a refactor can't silently drop the requirement along with
    the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert {"join", "_prefill", "step", "_evict"} <= set(entries[
        ("paddle_tpu/serving/decode/engine.py", "class:DecodeEngine")])


def test_injection_lint_covers_disagg_entry_points():
    """The disagg PR's contract: the chaos suite must be able to kill the
    prefill side of a KV handoff (kv.export), tear the wire mid-transfer
    (kv.transfer), fail decode-side adoption (kv.adopt), and break routing
    itself (disagg.route) — every edge has to land as a typed refusal or a
    journaled fallback re-prefill, never a lost stream. Guard the MANIFEST
    so a refactor can't silently drop the requirement along with the
    hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert {"export", "transfer", "adopt"} <= set(entries[
        ("paddle_tpu/serving/decode/kv_migrate.py", "class:KVMigrator")])
    assert "route" in entries[
        ("paddle_tpu/serving/disagg.py", "class:DisaggController")]


def test_injection_lint_covers_prefix_spec_entry_points():
    """The prefix-sharing/speculation PR's contract: the radix match
    (prefix.lookup must degrade to a cold miss), indexing (prefix.share
    stays cold), eviction (prefix.evict must still complete), the draft
    pass (spec.draft falls back to a plain tick), and the verify pass
    (spec.verify must resolve as a token-identical replay) all stay
    chaos-testable. Guard the MANIFEST so a refactor can't silently drop
    the requirement along with the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert {"lookup", "share", "evict", "clear"} <= set(entries[
        ("paddle_tpu/serving/decode/prefix.py", "class:PrefixCache")])
    assert "propose" in entries[
        ("paddle_tpu/serving/decode/specdecode.py", "class:SpecDecoder")]
    assert "_spec_round" in entries[
        ("paddle_tpu/serving/decode/engine.py", "class:DecodeEngine")]


def test_injection_lint_covers_reducer_entry_points():
    """The compiled-by-default PR's contract: the bucketed reducer's
    fused-bucket dispatch (reducer.flush) stays chaos-testable — it is
    the only point where a collective fault can land inside the
    backward/communication overlap window, so dropping the hook would
    make that whole failure mode unschedulable. Guard the MANIFEST so a
    refactor can't silently drop the requirement along with the hook."""
    import ast
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    required = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "REQUIRED" for t in node.targets))
    manifest = ast.literal_eval(required)
    entries = {(rel, scope): names for rel, scope, names in manifest}
    assert "_flush" in entries[
        ("paddle_tpu/distributed/reducer.py", "class:Reducer")]
    sites = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "SITES" for t in node.targets))
    assert "reducer.flush" in ast.literal_eval(sites)


def test_metric_name_lint_passes_on_tree():
    r = _run(REPO / "tools" / "check_metric_names.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "metric-name lint OK" in r.stdout


def test_metric_name_lint_manifest_guard():
    """The observability PR's contract: the step-phase / registry metric
    subsystems stay registered and the grandfather list stays frozen (new
    names must pass subsystem.noun_unit, not grow the escape hatch). Guard
    the lint's own manifests so a refactor can't silently gut the check."""
    import ast
    src = (REPO / "tools" / "check_metric_names.py").read_text()
    tree = ast.parse(src)

    def _assigned(name):
        return next(
            node.value for node in ast.walk(tree)
            if isinstance(node, ast.Assign)
            and any(getattr(t, "id", None) == name for t in node.targets))

    subsystems = set(ast.literal_eval(_assigned("SUBSYSTEMS")))
    assert {"steptimer", "metrics", "serving", "io", "integrity",
            "ckpt", "compiled_step", "rollout", "decode",
            "slo", "trace", "prefix", "spec"} <= subsystems
    units = set(ast.literal_eval(_assigned("UNITS")))
    assert {"ms", "total", "per_sec"} <= units
    grandfathered = set(ast.literal_eval(_assigned("GRANDFATHERED")))
    # frozen: pre-convention names only — anything new must follow the
    # pattern instead of being added here
    assert grandfathered <= {"straggler.rank{}", "{}.{}"}


def test_span_name_lint_passes_on_tree():
    r = _run(REPO / "tools" / "check_span_names.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "span-name lint OK" in r.stdout


def test_span_name_lint_manifest_guard():
    """The request-tracing PR's contract: the fixed span vocabulary the
    explain tool / merge overlay / docs table all key on stays registered,
    and the trace-shaped call sites stay linted. Guard the lint's own
    manifests so a refactor can't silently gut the check."""
    import ast
    src = (REPO / "tools" / "check_span_names.py").read_text()
    tree = ast.parse(src)

    def _assigned(name):
        return next(
            node.value for node in ast.walk(tree)
            if isinstance(node, ast.Assign)
            and any(getattr(t, "id", None) == name for t in node.targets))

    spans = set(ast.literal_eval(_assigned("SPAN_NAMES")))
    assert {"client.submit", "server.admit", "batcher.queue",
            "batcher.batch_assemble", "scheduler.dispatch", "replica.exec",
            "engine.join", "engine.prefill_chunk", "engine.decode_tick",
            "engine.kv_wait"} <= spans
    calls = set(ast.literal_eval(_assigned("SPAN_CALLS")))
    assert {"begin_span", "record_span", "span"} <= calls


def test_span_manifest_matches_tracer_vocabulary():
    """The lint manifest and the tracer's own SPAN_NAMES tuple must not
    drift: the manifest is where review happens, the tracer constant is
    what runtime consumers import."""
    import ast
    lint_src = (REPO / "tools" / "check_span_names.py").read_text()
    lint_names = set(ast.literal_eval(next(
        node.value for node in ast.walk(ast.parse(lint_src))
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "SPAN_NAMES"
                for t in node.targets))))
    tracer_src = (REPO / "paddle_tpu" / "profiler" / "tracing.py").read_text()
    tracer_names = set(ast.literal_eval(next(
        node.value for node in ast.walk(ast.parse(tracer_src))
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "SPAN_NAMES"
                for t in node.targets))))
    assert lint_names == tracer_names


def test_compiled_step_flags_registered():
    """The compiled-step knobs stay registered with their contracted
    defaults: FLAGS_compiled_step ships ON (compiled-by-default PR —
    eager stays the debug/parity oracle behind `0`), the retrace-storm
    bound stays finite, prefetch/donation stay on, and the reducer's
    bucket cap stays at the measured 25 MiB sweet spot. Parsed from
    source, not live state, so another test mutating flags can't flake
    this guard."""
    import ast
    src = (REPO / "paddle_tpu" / "framework" / "flags.py").read_text()
    tree = ast.parse(src)
    defaults_node = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.AnnAssign)
        and getattr(node.target, "id", None) == "_FLAGS")
    defaults = {}
    for key, val in zip(defaults_node.keys, defaults_node.values):
        try:
            defaults[ast.literal_eval(key)] = ast.literal_eval(val)
        except ValueError:
            pass  # computed defaults (e.g. 1 << 20) — not ours
    assert defaults["FLAGS_compiled_step"] is True
    assert int(defaults["FLAGS_compiled_step_max_retraces"]) >= 1
    assert defaults["FLAGS_input_prefetch"] is True
    assert defaults["FLAGS_donate_state_buffers"] is True
    assert int(defaults["FLAGS_reducer_bucket_mb"]) >= 1


def test_decode_flags_registered():
    """The decode PR's knobs stay registered with their contracted
    defaults: weight-only quantization ships OFF (opt-in via
    FLAGS_decode_quantize=int8), and the KV pool / prefill-ration geometry
    stays positive. Parsed from source, not live state."""
    import ast
    src = (REPO / "paddle_tpu" / "framework" / "flags.py").read_text()
    tree = ast.parse(src)
    defaults_node = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.AnnAssign)
        and getattr(node.target, "id", None) == "_FLAGS")
    defaults = {}
    for key, val in zip(defaults_node.keys, defaults_node.values):
        try:
            defaults[ast.literal_eval(key)] = ast.literal_eval(val)
        except ValueError:
            pass
    assert defaults["FLAGS_decode_quantize"] == ""
    assert int(defaults["FLAGS_decode_block_size"]) >= 1
    assert int(defaults["FLAGS_decode_kv_blocks"]) >= 1
    assert int(defaults["FLAGS_decode_prefill_chunk"]) >= 1
    assert int(defaults["FLAGS_decode_max_new_tokens"]) >= 1


def test_disagg_flags_registered():
    """The disagg PR's knobs stay registered with their contracted
    defaults: the burn window and high-watermark drive per-stage admission
    (BurnGate), and the in-flight migration cap bounds decode-side memory
    exposure during handoffs. Parsed from source, not live state."""
    import ast
    src = (REPO / "paddle_tpu" / "framework" / "flags.py").read_text()
    tree = ast.parse(src)
    defaults_node = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.AnnAssign)
        and getattr(node.target, "id", None) == "_FLAGS")
    defaults = {}
    for key, val in zip(defaults_node.keys, defaults_node.values):
        try:
            defaults[ast.literal_eval(key)] = ast.literal_eval(val)
        except ValueError:
            pass
    assert float(defaults["FLAGS_disagg_burn_window"]) > 0
    assert float(defaults["FLAGS_disagg_burn_high"]) > 0
    assert int(defaults["FLAGS_disagg_max_inflight"]) >= 1


def test_prefix_spec_flags_registered():
    """The prefix-sharing/speculation PR's knobs stay registered with
    their contracted defaults: both ship OFF (sharing is opt-in per
    deployment; spec_k=0 disables drafting) so the features never change
    serving behavior until explicitly enabled. Parsed from source, not
    live state."""
    import ast
    src = (REPO / "paddle_tpu" / "framework" / "flags.py").read_text()
    tree = ast.parse(src)
    defaults_node = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.AnnAssign)
        and getattr(node.target, "id", None) == "_FLAGS")
    defaults = {}
    for key, val in zip(defaults_node.keys, defaults_node.values):
        try:
            defaults[ast.literal_eval(key)] = ast.literal_eval(val)
        except ValueError:
            pass
    assert defaults["FLAGS_decode_prefix_sharing"] is False
    assert int(defaults["FLAGS_decode_spec_k"]) == 0


def test_trace_merge_help_smoke():
    r = _run(REPO / "tools" / "trace_merge.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "timeline" in r.stdout


def test_request_trace_help_smoke():
    r = _run(REPO / "tools" / "request_trace.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "--explain" in r.stdout


def test_replay_step_help_smoke():
    r = _run(REPO / "tools" / "replay_step.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "hardware_sdc" in r.stdout


def test_bench_regression_gate_help_smoke():
    r = _run(REPO / "tools" / "check_bench_regression.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr


def test_flight_recorder_diff_help_smoke():
    r = _run(REPO / "tools" / "flight_recorder_diff.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "divergent" in r.stdout


def test_ckpt_inspect_help_smoke():
    r = _run(REPO / "tools" / "ckpt_inspect.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "manifest" in r.stdout


def test_serving_bench_help_smoke():
    r = _run(REPO / "tools" / "serving_bench.py", "--help")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "shed rate" in r.stdout


def test_serving_bench_overload_smoke():
    """The overload sweep must keep demonstrating graceful degradation:
    at 10x offered load goodput stays positive, every request terminates,
    and p99 holds under the deadline. Fake clock + synthetic predictor, so
    this runs in ~2s of wall time despite simulating seconds of traffic."""
    import json
    r = _run(REPO / "tools" / "serving_bench.py", "--overload", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["graceful_degradation"] is True
    ten_x = [p for p in report["results"] if p["multiplier"] >= 10.0]
    assert ten_x, report
    for point in ten_x:
        assert point["completed"] > 0
        assert point["unterminated"] == 0
        assert point["shed"] == point["shed_with_hint"]
    # tracing contract: every shed/deadline/errored request has a retained
    # trace, retention stays inside the tail+head policy, and per-request
    # tracer overhead stays under 1% of the modeled service time
    for point in report["results"]:
        assert point["trace_coverage_ok"] is True, point
        assert point["trace_bound_ok"] is True, point
        assert point["traces_exceptional"] == point["exceptional"]
    assert report["results"][0]["trace_overhead_pct"] < 1.0


def test_serving_bench_decode_smoke():
    """The decode sweep must keep demonstrating continuous-batching SLOs:
    at every offered-load multiplier all streams terminate, sheds carry
    retry hints, compiles stay bounded by the bucket set, and goodput plus
    TTFT/TPOT percentiles land in extra.* for the bench regression gate.
    Fake clock, so this runs in ~1s of wall time."""
    import json
    r = _run(REPO / "tools" / "serving_bench.py", "--decode", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["decode_ok"] is True
    for point in report["results"]:
        assert point["completed"] > 0
        assert point["unterminated"] == 0
        assert point["shed"] == point["shed_with_hint"]
        assert point["compiles"] <= point["compile_bound"]
        assert point["trace_coverage_ok"] is True, point
        assert point["trace_bound_ok"] is True, point
    assert report["results"][0]["trace_overhead_pct"] < 1.0
    extra = report["extra"]
    assert extra["decode_goodput_tokens_per_sec"] > 0
    for k in ("decode_ttft_p50_ms", "decode_ttft_p99_ms",
              "decode_tpot_p50_ms", "decode_tpot_p99_ms"):
        assert isinstance(extra[k], (int, float)), (k, extra)


def test_serving_bench_prefix_share_smoke():
    """The prefix-sharing A/B must keep demonstrating the PR's headline:
    on the identical seeded shared-prefix mix and KV budget, warm-prefix
    TTFT p99 improves >= 5x over the no-sharing baseline and goodput
    >= 2x; speculation accepts drafts while staying token-identical to
    greedy decode; and the chaos leg (decode/prefix/spec sites armed)
    leaks nothing — zero leaked blocks and zero live refcounts after
    drain. Fake clock, so this runs in a few seconds of wall time."""
    import json
    r = _run(REPO / "tools" / "serving_bench.py",
             "--decode", "--prefix-share", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["prefix_ok"] is True
    results = report["results"]
    assert results["warm_ttft_gain"] >= 5.0
    assert results["goodput_gain"] >= 2.0
    assert results["spec_token_identical"] is True
    assert results["spec_parity_accept_ratio"] > 0.0
    for leg in results["legs"]:
        assert leg["unterminated"] == 0
        assert leg["leaked_blocks"] == 0
        assert leg["kv_used_after_drain"] == 0
        assert leg["nonzero_refcounts_after_drain"] == 0
    chaos = results["legs"][-1]
    assert chaos["chaos"] is True and chaos["completed"] > 0
    extra = report["extra"]
    assert extra["prefix_warm_ttft_gain"] >= 5.0
    assert extra["prefix_goodput_gain"] >= 2.0


def test_serving_bench_rollout_soak_smoke():
    """The rollout soak must keep demonstrating zero-downtime hot-swap:
    traffic flows while checkpoints commit mid-stream (one of them
    poisoned), the fleet converges to the newest good version, the poison
    rolls back, and not a single request is shed or mis-stamped. Fake clock,
    so this simulates seconds of traffic in ~2s of wall time."""
    import json
    r = _run(REPO / "tools" / "serving_bench.py", "--rollout-soak", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["rollout_soak_ok"] is True
    gates = report["results"]["gates"]
    for gate in ("zero_shed", "zero_unterminated", "stamps_match_outputs",
                 "converged_to_newest_good", "poison_rolled_back"):
        assert gates[gate] is True, (gate, report["results"])


def test_serving_bench_disagg_smoke():
    """The disagg comparison must keep demonstrating the PR's headline:
    at the top load multiplier with a bimodal prompt mix, the
    prefill/decode-disaggregated fleet beats the colocated baseline on
    both TTFT p99 and TPOT p99, an injected prefill death mid-handoff
    resolves as a fallback re-prefill with zero streams lost, every shed
    carries a retry hint, and no KV block leaks. Fake clock, so this runs
    in a few seconds of wall time."""
    import json
    r = _run(REPO / "tools" / "serving_bench.py", "--disagg", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["disagg_ok"] is True
    for point in report["results"]:
        assert point["unterminated"] == 0
        assert point["leaked_blocks"] == 0
        gates = point["gates"]
        assert gates["zero_lost_streams"] is True, point
        assert gates["sheds_hinted"] is True, point
        assert gates["zero_leaked_blocks"] is True, point
    top = report["results"][-1]
    assert top["injected_prefill_death"] is True
    assert top["gates"]["ttft_p99_better"] is True, top
    assert top["gates"]["tpot_p99_better"] is True, top
    assert top["gates"]["fallback_exercised"] is True, top
    assert top["fallback_prefills"] >= 1
    extra = report["extra"]
    for k in ("disagg_ttft_p99_ms", "disagg_tpot_p99_ms"):
        assert isinstance(extra[k], (int, float)), (k, extra)


def test_injection_site_manifest_matches_tree():
    """The chaos-campaign PR's contract: SITES in
    tools/check_injection_points.py is the single source of truth the
    schedule sampler draws from (via known_sites()), so it must name
    exactly the injection sites present in the tree — a site added
    without a manifest entry would never be scheduled (silent coverage
    hole), and a stale entry would burn schedule rules on a site that
    can never fire. Source-level on purpose: the literal must stay
    ast-parseable."""
    import ast
    import re
    src = (REPO / "tools" / "check_injection_points.py").read_text()
    tree = ast.parse(src)
    lit = next(
        node.value for node in ast.walk(tree)
        if isinstance(node, ast.Assign)
        and any(getattr(t, "id", None) == "SITES" for t in node.targets))
    manifest = set(ast.literal_eval(lit))
    pat = re.compile(
        r'(?:maybe_inject|should_inject|fault_point)\(\s*[\'"]([a-z0-9_.]+)[\'"]')
    in_tree = set()
    for path in (REPO / "paddle_tpu").rglob("*.py"):
        in_tree |= set(pat.findall(path.read_text()))
    assert manifest == in_tree, (
        f"missing from SITES: {sorted(in_tree - manifest)}; "
        f"stale in SITES: {sorted(manifest - in_tree)}")


def test_chaos_campaign_smoke_gate():
    """The chaos-campaign gate: >=25 mixed fake-clock episodes across the
    training and serving scenarios, sampled from the full injection-site
    manifest, must terminate with ZERO invariant violations (typed
    termination, no KV leaks, journal consistency, bounded progress,
    training-loss parity, metrics/journal agreement) while evaluating at
    least 90% of the manifest's sites. Deterministic by construction, so
    a failure here is a real regression and the printed bundle path holds
    a shrunken repro."""
    import json
    r = _run(REPO / "tools" / "chaos_campaign.py", "--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["episodes_run"] >= 25
    assert report["violations_total"] == 0, report["artifact_bundles"]
    cov = report["coverage"]
    assert cov["ratio"] >= 0.9, cov["uncovered_sites"]
    # the expert-parallel sites are in the sampled manifest AND the ≥90%
    # bar holds with them present: the TrainingScenario MoE segment must
    # keep evaluating them, not dilute coverage by merely registering them
    sys.path.insert(0, str(REPO / "tools"))
    try:
        from check_injection_points import known_sites
    finally:
        sys.path.pop(0)
    moe_sites = {"moe.dispatch", "moe.combine", "moe.resize"}
    assert moe_sites <= set(known_sites())
    assert not moe_sites & set(cov["uncovered_sites"]), cov
    # both scenarios actually ran
    assert {e["scenario"] for e in report["episodes"]} == {"training",
                                                           "serving"}
