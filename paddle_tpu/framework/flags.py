"""Global flags registry.

Reference parity: paddle/fluid/platform/flags.cc (PADDLE_DEFINE_EXPORTED gflags)
+ paddle.set_flags/get_flags (pybind/global_value_getter_setter.cc). TPU-native:
flags that controlled CUDA allocator/cudnn behavior are kept as named knobs
where they have an XLA analog, else accepted and ignored (documented inert).
"""
from __future__ import annotations

import os
from typing import Any

_FLAGS: dict[str, Any] = {
    # numerical sanitizer (framework/details/nan_inf_utils_detail.cc parity)
    "FLAGS_check_nan_inf": False,
    # determinism (FLAGS_cudnn_deterministic parity): XLA is deterministic by
    # default, and no kernel's tiles are drawn by a clock
    "FLAGS_deterministic": True,
    "FLAGS_cudnn_deterministic": True,
    # eager-op log level (imperative/tracer verbosity)
    "FLAGS_log_level": 0,
    # to_static compilation cache size
    "FLAGS_max_cached_programs": 64,
    # donate buffers for jitted train steps (memory optimization)
    "FLAGS_donate_state_buffers": True,
    # whole-step compilation (jit/compiled_step.py, docs/compiled_step.md):
    # route hapi train_batch/fit and the bench LM lanes through ONE donated,
    # sharding-annotated jitted program per step (fwd+bwd+optimizer). ON by
    # default since the compiled lane passed its eager-parity gates; set 0
    # to opt back into eager, which stays the debug/parity oracle.
    "FLAGS_compiled_step": True,
    # fused-bucket size cap (MB) for the eager DP gradient Reducer
    # (distributed/reducer.py, docs/distributed.md): backward hooks fire a
    # bucket's single async allreduce the moment it fills, overlapping the
    # collective with the rest of backward
    "FLAGS_reducer_bucket_mb": 25,
    # distinct input signatures one compiled step fn may trace before the
    # retrace-storm guard warns through the flight recorder; 0 disables
    "FLAGS_compiled_step_max_retraces": 8,
    # double-buffered host->device input prefetch in the hapi fit loop:
    # step N+1's batch is staged while step N runs (drops step/input_wait +
    # step/h2d). The loader's exact-resume cursor only advances when a batch
    # is actually consumed, so checkpoint/resume stays exact.
    "FLAGS_input_prefetch": True,
    # resilience subsystem (paddle_tpu/resilience, docs/resilience.md)
    # fault-injection spec, e.g. "fs.upload:0.3,collective.all_reduce:0.1"
    "FLAGS_fault_injection": "",
    "FLAGS_fault_injection_seed": 0,
    # retry policy defaults for FS transfers / heartbeat / ckpt staging
    "FLAGS_retry_max_attempts": 3,
    "FLAGS_retry_backoff_base": 0.5,
    # consecutive non-finite steps before StepGuard rolls back to the last
    # auto-checkpoint
    "FLAGS_guard_max_bad_steps": 3,
    # hang detection (paddle_tpu/resilience/{watchdog,recorder}.py):
    # deadline for one eager collective / p2p op / elastic store roundtrip
    "FLAGS_collective_timeout": 300.0,
    # how often the watchdog monitor thread checks section deadlines
    "FLAGS_watchdog_interval": 5.0,
    # flight-recorder ring size (entries); dumps land in
    # PADDLE_TPU_ARTIFACTS_DIR as flight_recorder_rank<N>.json
    "FLAGS_flight_recorder_size": 1024,
    # coordinated elastic recovery (paddle_tpu/resilience/recovery.py):
    # in-job restart budget before RecoveryExhausted
    "FLAGS_recovery_max_restarts": 3,
    # how long a re-rendezvous waits for replacement ranks before
    # proceeding scaled-in at np_min (or failing below it)
    "FLAGS_recovery_rendezvous_timeout": 300.0,
    # exponential backoff base between restarts (doubles per restart)
    "FLAGS_recovery_backoff_base": 1.0,
    # consecutive healthy steps (clean RecoveryManager.check passes /
    # note_progress calls) after which the restart budget refills;
    # 0 = per-job-lifetime budget
    "FLAGS_recovery_restart_reset_steps": 100,
    # serving subsystem (paddle_tpu/serving, docs/serving.md):
    # watchdog deadline for one dispatched batch (assemble→run→reply)
    "FLAGS_serving_step_timeout": 60.0,
    # bounded request queue; admission sheds (ServerOverloaded) beyond this
    "FLAGS_serving_max_queue": 256,
    # AIMD admission: target per-batch execution latency; at/under the
    # target the in-system limit creeps up, over it the limit is cut x0.7
    "FLAGS_serving_admission_target_ms": 100.0,
    # base retry_after hint (seconds) carried by ServerOverloaded sheds
    "FLAGS_serving_retry_after": 0.1,
    # circuit breaker: failures/timeouts within the rolling window that
    # trip a replica's breaker open, and the cooldown before the half-open
    # preflight+canary probe may run
    "FLAGS_serving_breaker_failures": 5,
    "FLAGS_serving_breaker_window": 30.0,
    "FLAGS_serving_breaker_cooldown": 10.0,
    # hedged dispatch: fraction of dispatches allowed a second (hedged)
    # attempt, and the floor on the p99-derived hedge delay; budget 0
    # disables hedging
    "FLAGS_serving_hedge_budget": 0.05,
    "FLAGS_serving_hedge_min_ms": 10.0,
    # live rollout (serving/rollout.py, docs/serving.md "Live rollout"):
    # seconds between manifest-watcher polls of the checkpoint root
    "FLAGS_rollout_poll_interval": 30.0,
    # golden-request gate: max relative drift of canary outputs vs the
    # incumbent's captured outputs (NaN/Inf always fail). Generous default
    # — a legitimately retrained model moves its outputs; pass a custom
    # golden_check for model-specific quality gates
    "FLAGS_rollout_golden_max_drift": 1.0,
    # bound on waiting for one stale-version replica to drain during a
    # roll before it is force-removed (fenced: late results dropped)
    "FLAGS_rollout_drain_timeout": 60.0,
    # consecutive failed controller steps mid-ROLLING before the roll is
    # abandoned and rolled back to the incumbent version
    "FLAGS_rollout_max_step_failures": 3,
    # continuous-batching decode (serving/decode/, docs/serving.md
    # "Continuous-batching decode"): paged KV-cache pool geometry —
    # tokens per block, blocks in the fixed pool
    "FLAGS_decode_block_size": 16,
    "FLAGS_decode_kv_blocks": 256,
    # prefill ration: at most this many prompt tokens absorbed per engine
    # step (one stream per step) so long prompts never stall decode
    "FLAGS_decode_prefill_chunk": 64,
    # default generation length cap when the request doesn't set one
    "FLAGS_decode_max_new_tokens": 64,
    # weight-only quantization for decode replicas at load time
    # ("" = off, "int8" = per-channel absmax int8; slim/ptq.py)
    "FLAGS_decode_quantize": "",
    # prefix-sharing KV cache (serving/decode/prefix.py, docs/serving.md
    # "Prefix sharing & speculative decoding"): warm joins adopt
    # radix-matched cached prompt pages (refcounted, copy-on-write)
    "FLAGS_decode_prefix_sharing": False,
    # speculative decoding draft length: the draft proposes up to this
    # many tokens per tick, verified in one batched target step
    # (0 = off; also needs a DraftModel on the DecodeConfig)
    "FLAGS_decode_spec_k": 0,
    # disaggregated prefill/decode serving (serving/disagg.py,
    # docs/serving.md "Disaggregated prefill/decode"): burn-rate window
    # (seconds) the per-stage BurnGates read, the burn multiple above
    # which a stage refuses new work, and the cap on handoffs in flight
    # between the prefill and decode classes
    "FLAGS_disagg_burn_window": 60.0,
    "FLAGS_disagg_burn_high": 2.0,
    "FLAGS_disagg_max_inflight": 8,
    # hardware health & SDC defense (resilience/{integrity,health}.py):
    # steps between cross-replica parameter-checksum consensus rounds;
    # 0 disables in-training SDC detection
    "FLAGS_integrity_check_interval": 100,
    # how long one consensus round waits for peer digests before voting
    # with whoever reported (a dead peer must not hang the check)
    "FLAGS_integrity_consensus_timeout": 30.0,
    # run the known-answer test at startup / re-rendezvous / replica restart
    "FLAGS_preflight_checks": True,
    # how long a quarantined.<rank> marker excludes that rank from
    # rendezvous (seconds); after expiry a repaired host may rejoin
    "FLAGS_quarantine_ttl": 3600.0,
    # straggler detector: rolling window (steps) and flag threshold as a
    # multiple of the group-median step time
    "FLAGS_straggler_window": 50,
    "FLAGS_straggler_threshold": 3.0,
    # opt-in: a rank that detects ITSELF straggling takes the quarantine
    # exit (off by default — slowness is often the network, not the host)
    "FLAGS_straggler_quarantine": False,
    # steps of replay material (rng key + raw inputs) kept for
    # tools/replay_step.py SDC classification
    "FLAGS_replay_buffer_size": 8,
    # rotate the recovery journal past this size, keeping two segments;
    # 0 = unbounded
    "FLAGS_journal_max_bytes": 1 << 20,
    # zero-stall checkpointing (resilience/snapshot.py, docs/resilience.md):
    # route hapi Model.save / ModelCheckpoint / save_hybrid_checkpoint
    # through the AsyncCheckpointer — foreground cost is only the
    # device→host snapshot; serialize + sha256 + atomic manifest commit run
    # on the background committer thread. Off = sync fallback (everything
    # in the foreground, errors raise at the call site).
    "FLAGS_async_checkpoint": False,
    # keep-last-K manifest retention (per checkpoint root); the newest
    # committed manifest and every file it references are never deleted.
    # 0 = keep everything.
    "FLAGS_ckpt_keep": 3,
    # bound on waiting for pending background commits at preemption /
    # recovery-restore time (seconds)
    "FLAGS_ckpt_flush_timeout": 60.0,
    # observability (paddle_tpu/profiler/{metrics,steptimer}.py,
    # docs/observability.md): step-phase attribution master switch
    "FLAGS_steptimer": True,
    # steps between block_until_ready samples that split device time from
    # host dispatch time; 0 = never sync (host-dispatch times only)
    "FLAGS_steptimer_sync_interval": 16,
    # seconds between metrics snapshots written to PADDLE_TPU_ARTIFACTS_DIR
    # (metrics_rank<N>.prom / .jsonl); 0 disables the exporter
    "FLAGS_metrics_export_interval": 60.0,
    # request-level tracing master switch (profiler/tracing.py): every
    # serving/decode request is traced; tail-based retention decides which
    # traces are flushed to request_traces_rank<N>.jsonl
    "FLAGS_request_tracing": True,
    # a trace that ends slower than this (ms) is retained even when it
    # terminated cleanly — the "slow but not failed" tail
    "FLAGS_trace_slow_ms": 1000.0,
    # deterministic head sample: every Nth trace is retained regardless of
    # outcome (baseline for comparing against the exceptional tail);
    # 0 disables head sampling
    "FLAGS_trace_head_sample": 100,
    # bound on simultaneously live traces; past it new requests run
    # untraced (degrade, never grow without bound)
    "FLAGS_trace_ring": 4096,
    # inert reference flags accepted for script compatibility
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_use_standalone_executor": True,
}


def _coerce(cur, val):
    if isinstance(cur, bool):
        if isinstance(val, str):
            return val.lower() in ("1", "true", "yes")
        return bool(val)
    if isinstance(cur, int):
        return int(val)
    if isinstance(cur, float):
        return float(val)
    return val


# env overrides at import (gflags env behavior)
for _k in list(_FLAGS):
    if _k in os.environ:
        _FLAGS[_k] = _coerce(_FLAGS[_k], os.environ[_k])


def _native_lib():
    """The C++ registry (csrc/flags.cc) is the authoritative store when the
    native runtime is available; this dict then acts as a typed mirror."""
    from ..core import native
    lib = native.try_load()
    if lib is None:
        return None
    if not getattr(_native_lib, "_registered", False):
        for k, v in _FLAGS.items():
            ty = (0 if isinstance(v, bool) else 1 if isinstance(v, int)
                  else 2 if isinstance(v, float) else 3)
            lib.pt_flag_define(k.encode(), ty, str(v).encode(), b"")
        _native_lib._registered = True
    return lib


def set_flags(flags: dict):
    lib = _native_lib()
    for k, v in flags.items():
        if k in _FLAGS:
            _FLAGS[k] = _coerce(_FLAGS[k], v)
        else:
            _FLAGS[k] = v
        if lib is not None:
            ty = (0 if isinstance(_FLAGS[k], bool)
                  else 1 if isinstance(_FLAGS[k], int)
                  else 2 if isinstance(_FLAGS[k], float) else 3)
            lib.pt_flag_define(k.encode(), ty, str(_FLAGS[k]).encode(), b"")
            lib.pt_flag_set(k.encode(), str(_FLAGS[k]).encode())
    if "FLAGS_check_nan_inf" in flags:
        # eager coverage (per-op output scan); jitted coverage comes from the
        # resilience StepGuard, which reads this flag at construction
        # (hapi.Model.fit builds one automatically when the flag is set)
        from ..core.dispatch import set_debug
        set_debug(check_nan_inf=_FLAGS["FLAGS_check_nan_inf"])
    if "FLAGS_fault_injection" in flags or \
            "FLAGS_fault_injection_seed" in flags:
        from ..resilience import faults
        faults.reconfigure_from_flags()


def get_flags(flags=None):
    if flags is None:
        return dict(_FLAGS)
    if isinstance(flags, str):
        flags = [flags]
    return {k: _FLAGS.get(k) for k in flags}


def get_flag(name, default=None):
    return _FLAGS.get(name, default)
