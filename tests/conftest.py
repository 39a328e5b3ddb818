"""Test configuration.

Mirrors the reference's CPU-everywhere testability (SURVEY.md §4): tests run on
a virtual 8-device CPU mesh so sharding/collective paths compile and execute
without TPU hardware.
"""
import os

# force CPU; set PADDLE_TPU_TEST_DEVICE=tpu to run the suite on the real chip.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# keep compile times sane on the 1-core CI box
os.environ.setdefault("JAX_ENABLE_X64", "0")
# the persistent XLA compilation cache is placed by paddle_tpu/__init__.py
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache): repeat
# suite runs skip recompiles

if os.environ.get("PADDLE_TPU_TEST_DEVICE", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def paddle():
    import paddle_tpu
    return paddle_tpu


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu
    paddle_tpu.seed(1234)
    yield


@pytest.fixture(autouse=True)
def _lock_order(request):
    """Chaos tests run under the runtime lock-order tracker: every lock
    created during the test is wrapped, per-thread acquisition order is
    recorded, and a cyclic order (ABBA) fails the test deterministically
    — no contention or sleeps needed (docs/static_analysis.md)."""
    if request.node.get_closest_marker("chaos") is None:
        yield
        return
    from paddle_tpu.analysis import lockorder
    with lockorder.tracking(mode="record") as tracker:
        yield
    assert not tracker.violations, (
        "lock-order inversion(s) recorded during chaos test:\n" +
        "\n".join(v.args[0] for v in tracker.violations))


@pytest.fixture(autouse=True)
def _trace_san(request):
    """Chaos and compiled-step tests run under the runtime trace
    sanitizer: compiles routed through the step wrappers are counted per
    signature and host syncs are watched inside step/compute, so a
    steady-state retrace or an in-phase sync fails the test
    deterministically (docs/compiled_step.md, 'Trace hygiene'). Tests
    that exercise retrace pathologies on purpose opt out with
    ``@pytest.mark.allow_retrace``."""
    chaos = request.node.get_closest_marker("chaos") is not None
    compiled = "compiled" in request.node.fspath.basename
    if (not (chaos or compiled)
            or request.node.get_closest_marker("allow_retrace") is not None):
        yield
        return
    from paddle_tpu.analysis import tracesan
    with tracesan.tracking(mode="record") as san:
        yield
    assert not san.violations, (
        "trace-safety violation(s) recorded:\n" +
        "\n".join(v.args[0] for v in san.violations))
