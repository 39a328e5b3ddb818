"""Autotune cache (ISSUE 5 tentpole).

Covers: search picks the measured winner and persists it; a warm cache
(second tuner = second process) performs ZERO timed searches; corrupt/torn
cache files are ignored and rebuilt; a kernel-source-hash bump invalidates
stale entries; unsearchable placements (CPU/interpret — this suite) get the
deterministic fallback without timing anything; on a searchable placement a
failing candidate is counted, an all-fail search and a failing required
candidate raise. Which kernel an op runs is tests/test_kernel_choice.py.
"""
import json

import numpy as np
import pytest

from paddle_tpu.ops import autotune
from paddle_tpu.ops.autotune import AutotuneError, Autotuner


class ScriptedMeasure:
    """measure_fn double: returns scripted times keyed by the candidate tag
    build() embeds, and counts invocations."""

    def __init__(self, times):
        self.times = times
        self.calls = 0

    def __call__(self, fn, args):
        self.calls += 1
        return self.times[fn[1]]  # fn = ("cand", tag) from _build


def _build(cand):
    return ("cand", cand)


def _get(tuner, version="v1", fallback="b"):
    return tuner.get(
        "testop", "sig1", candidates=("a", "b", "c"), build=_build,
        make_args=lambda: (), fallback=fallback, version=version)


@pytest.fixture(autouse=True)
def _reset_counters():
    autotune.reset_counters()
    yield


def _tuner(tmp_path, times, searchable=True):
    return Autotuner(cache_dir=str(tmp_path),
                     measure_fn=ScriptedMeasure(times),
                     searchable=lambda: searchable)


class TestAutotuner:
    def test_search_picks_fastest_and_memoizes(self, tmp_path):
        t = _tuner(tmp_path, {"a": 3.0, "b": 1.0, "c": 2.0})
        assert _get(t) == "b"
        assert autotune.counters()["searches"] == 1
        assert t._measure.calls == 3
        # same-process second lookup: memo hit, no new timing
        assert _get(t) == "b"
        assert autotune.counters()["searches"] == 1
        assert t._measure.calls == 3
        assert autotune.counters()["mem_hits"] == 1

    def test_warm_cache_second_process_zero_searches(self, tmp_path):
        """Acceptance: search runs at most once per signature per cache
        lifetime — a fresh tuner over the same dir (= a second process)
        serves from disk with zero timed searches."""
        _get(_tuner(tmp_path, {"a": 3.0, "b": 1.0, "c": 2.0}))
        autotune.reset_counters()
        fresh = _tuner(tmp_path, {"a": 0.0, "b": 0.0, "c": 0.0})
        assert _get(fresh) == "b"
        assert autotune.counters()["searches"] == 0
        assert autotune.counters()["disk_hits"] == 1
        assert fresh._measure.calls == 0

    def test_corrupt_cache_ignored_and_rebuilt(self, tmp_path):
        _get(_tuner(tmp_path, {"a": 3.0, "b": 1.0, "c": 2.0}))
        (cache_file,) = tmp_path.glob("*.json")
        cache_file.write_text("{ not json !!")
        autotune.reset_counters()
        t2 = _tuner(tmp_path, {"a": 1.0, "b": 5.0, "c": 5.0})
        assert _get(t2) == "a"  # rebuilt from a fresh search
        assert autotune.counters()["searches"] == 1
        # and the file is valid JSON again
        rec = json.loads(cache_file.read_text())
        assert rec["value"] == "a"

    def test_torn_cache_file_is_a_miss(self, tmp_path):
        _get(_tuner(tmp_path, {"a": 3.0, "b": 1.0, "c": 2.0}))
        (cache_file,) = tmp_path.glob("*.json")
        full = cache_file.read_text()
        cache_file.write_text(full[: len(full) // 2])  # torn write
        t2 = _tuner(tmp_path, {"a": 5.0, "b": 5.0, "c": 1.0})
        assert _get(t2) == "c"

    def test_wrong_key_record_is_a_miss(self, tmp_path):
        """sha1-prefix collision / stale-layout safety: a record whose
        embedded key differs is ignored, not trusted."""
        t = _tuner(tmp_path, {"a": 3.0, "b": 1.0, "c": 2.0})
        _get(t)
        (cache_file,) = tmp_path.glob("*.json")
        rec = json.loads(cache_file.read_text())
        rec["key"] = "some|other|key"
        cache_file.write_text(json.dumps(rec))
        t2 = _tuner(tmp_path, {"a": 1.0, "b": 9.0, "c": 9.0})
        assert _get(t2) == "a"
        assert autotune.counters()["cache_errors"] >= 1

    def test_source_hash_bump_invalidates(self, tmp_path):
        _get(_tuner(tmp_path, {"a": 3.0, "b": 1.0, "c": 2.0}), version="v1")
        autotune.reset_counters()
        t2 = _tuner(tmp_path, {"a": 1.0, "b": 9.0, "c": 9.0})
        # kernel edited -> new version -> stale entry not served
        assert _get(t2, version="v2") == "a"
        assert autotune.counters()["searches"] == 1

    def test_unsearchable_returns_fallback_without_timing(self, tmp_path):
        t = _tuner(tmp_path, {"a": 1.0, "b": 2.0, "c": 3.0},
                   searchable=False)
        assert _get(t, fallback="c") == "c"
        assert t._measure.calls == 0
        assert autotune.counters()["fallbacks"] == 1
        # nothing persisted: a later on-device run still gets to search
        assert list(tmp_path.glob("*.json")) == []

    def test_all_candidates_failing_raises(self, tmp_path):
        """On a searchable placement a search whose every candidate fails is
        an error — never the fallback, and nothing is persisted."""
        def boom(fn, args):
            raise RuntimeError("does not fit")
        t = Autotuner(cache_dir=str(tmp_path), measure_fn=boom,
                      searchable=lambda: True)
        with pytest.raises(AutotuneError, match="every candidate failed"):
            _get(t, fallback="b")
        assert autotune.counters()["candidate_failures"] == 3
        assert "does not fit" in t.first_failure
        assert list(tmp_path.glob("*.json")) == []

    def test_failing_candidate_counted_and_loses(self, tmp_path):
        def measure(fn, args):
            if fn[1] == "a":
                raise RuntimeError("VMEM exceeded")
            return {"b": 2.0, "c": 1.0}[fn[1]]
        t = Autotuner(cache_dir=str(tmp_path), measure_fn=measure,
                      searchable=lambda: True)
        assert _get(t) == "c"
        c = autotune.counters()
        assert c["candidate_failures"] == 1 and c["searches"] == 1
        assert "'a'" in t.first_failure and "VMEM exceeded" in t.first_failure

    def test_required_candidate_failure_raises(self, tmp_path):
        def measure(fn, args):
            if fn[1] == "a":
                raise RuntimeError("mosaic refused")
            return 1.0
        t = Autotuner(cache_dir=str(tmp_path), measure_fn=measure,
                      searchable=lambda: True)
        with pytest.raises(AutotuneError, match="required candidate 'a'"):
            t.get("testop", "sig1", candidates=("a", "b"), build=_build,
                  make_args=lambda: (), fallback="b", version="v1",
                  required=("a",))
        assert list(tmp_path.glob("*.json")) == []

    def test_search_inside_a_trace_times_real_execution(self, tmp_path):
        """The block search is reached from inside the jitted fused probe:
        its probe arguments and calls must be concrete there, or the timing
        would be of staging a call."""
        import jax
        import jax.numpy as jnp
        seen = []

        def measure(fn, args):
            out = jax.tree_util.tree_leaves(fn(*args))[0]
            seen.append(isinstance(out, jax.core.Tracer))
            return 1.0

        t = Autotuner(cache_dir=str(tmp_path), measure_fn=measure,
                      searchable=lambda: True)

        def traced(x):
            t.get("testop", "sig", candidates=("a",),
                  build=lambda cand: jax.jit(lambda y: y * 2),
                  make_args=lambda: [jnp.ones((4,)) + 1], fallback="a")
            # a real kernel as the candidate: its body has primitives with
            # no operands (program_id), which only a trace may see
            import functools
            from paddle_tpu.ops.pallas import flash_attention as fa
            t.get("flash", "sig", candidates=((128, 128),),
                  build=lambda cand: functools.partial(
                      fa._flash_fwd_bh, causal=True, scale=1.0,
                      block_q=cand[0], block_k=cand[1], interpret=True),
                  make_args=lambda: fa._synth_bh([(1, 128, 64)] * 3,
                                                 [jnp.float32] * 3),
                  fallback=(128, 128))
            return x + 1

        jax.jit(traced)(1.0)
        assert seen == [False, False]
        assert autotune.counters()["candidate_failures"] == 0

    def test_tuple_values_roundtrip_through_disk(self, tmp_path):
        t = _tuner(tmp_path, {(512, 512): 2.0, (256, 512): 1.0})
        got = t.get("blocks", "s", candidates=((512, 512), (256, 512)),
                    build=_build, make_args=lambda: (),
                    fallback=(512, 512), version="v")
        assert got == (256, 512)
        t2 = _tuner(tmp_path, {})
        got2 = t2.get("blocks", "s", candidates=((512, 512), (256, 512)),
                      build=_build, make_args=lambda: (),
                      fallback=(512, 512), version="v")
        assert got2 == (256, 512) and isinstance(got2, tuple)

    def test_default_tuner_unsearchable_on_cpu(self):
        # this suite runs JAX_PLATFORMS=cpu: the process tuner must never
        # time anything (tier-1 hermeticity)
        assert not autotune.get_tuner().searchable()


class TestSignatureHelpers:
    def test_shape_bucket(self):
        assert autotune.shape_bucket((3, 100, 1024)) == (4, 128, 1024)
        assert autotune.shape_bucket((1,)) == (1,)

    def test_short_dtype(self):
        import jax.numpy as jnp
        assert autotune.short_dtype(jnp.bfloat16) == "bf16"
        assert autotune.short_dtype(jnp.float32) == "f32"

    def test_source_version_stable_and_real(self):
        v1 = autotune.source_version("paddle_tpu.ops.pallas.flash_attention")
        v2 = autotune.source_version("paddle_tpu.ops.pallas.flash_attention")
        assert v1 == v2 and v1 != "unknown" and len(v1) == 12


class TestFlashBlockFallbacks:
    def test_interpret_fallbacks_deterministic(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.flash_attention import (
            _tuned_bwd_blocks, _tuned_fwd_blocks)
        # interpret=True (this suite's regime): table answers, no tuner
        assert _tuned_fwd_blocks(64, 1024, 1024, 64, jnp.float32, True,
                                 True) == (512, 512)
        assert _tuned_bwd_blocks(64, 1024, 1024, 64, jnp.float32, True,
                                 True) == (512, 512)
        # the one-pass kernel's (block_q, block_k), whatever the dtype: its
        # VMEM limit follows the shapes, so bf16 halves nothing
        assert _tuned_bwd_blocks(64, 1024, 1024, 64, jnp.bfloat16, True,
                                 True) == (512, 512)
        # grouped heads take the same table
        assert _tuned_bwd_blocks(64, 4096, 4096, 64, jnp.bfloat16, True,
                                 True, group=4) == (512, 512)
        # short sequences clamp every entry to a divisor of s
        blocks = _tuned_bwd_blocks(8, 256, 256, 64, jnp.bfloat16, True, True)
        assert blocks == (256, 256)
        assert _tuned_bwd_blocks(8, 768, 768, 64, jnp.bfloat16, True,
                                 True) == (256, 256)

    def test_bwd_blocks_parity_tuned_vs_pinned(self):
        """The backward's tiles change scheduling, never numerics."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bwd, flash_attention_fwd)
        rng = np.random.RandomState(0)
        q, k, v = [jnp.asarray(rng.randn(1, 256, 2, 64).astype("float32"))
                   for _ in range(3)]
        out, lse = flash_attention_fwd(q, k, v, causal=True, scale=0.125)
        do = jnp.asarray(rng.randn(*out.shape).astype("float32"))
        tuned = flash_attention_bwd(q, k, v, out, lse, do, causal=True,
                                    scale=0.125)
        pinned = flash_attention_bwd(q, k, v, out, lse, do, causal=True,
                                     scale=0.125, block_q=128, block_k=64)
        for a, b in zip(tuned, pinned):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
