"""paddle.device parity (python/paddle/device/__init__.py)."""
from __future__ import annotations

from ..core.device import (  # noqa: F401
    CPUPlace, CUDAPlace, Place, TPUPlace, device_count, get_all_devices,
    get_device, is_compiled_with_cuda, is_compiled_with_tpu, set_device,
)

__all__ = ["set_device", "get_device", "TPUPlace", "CPUPlace", "CUDAPlace",
           "device_count", "is_compiled_with_tpu", "is_compiled_with_cuda",
           "synchronize", "cuda", "tpu"]


def synchronize(device=None):
    """Block until all queued work completes (cudaDeviceSynchronize parity —
    on jax, realize by blocking on a trivial transfer)."""
    import jax
    (jax.device_put(0) + 0).block_until_ready()


class _DeviceNS:
    """paddle.device.cuda-style namespace (streams are XLA-managed; the
    synchronization entry points exist for API parity)."""

    @staticmethod
    def device_count():
        return device_count("tpu")

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def current_stream(device=None):
        return None

    @staticmethod
    def stream_guard(stream):
        import contextlib
        return contextlib.nullcontext()

    @staticmethod
    def empty_cache():
        import gc
        gc.collect()

    @staticmethod
    def max_memory_allocated(device=None):
        import jax
        try:
            stats = jax.devices()[0].memory_stats()
            # reset semantics: peak restarts from CURRENT usage, never below
            return max(stats.get("bytes_in_use", 0),
                       stats.get("peak_bytes_in_use", 0)
                       - _PEAK_BASELINE["bytes"])
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        import jax
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_reserved(device=None):
        # backends without a reserved-bytes stat report 0 (bytes_limit is
        # total HBM capacity, NOT a reservation — see memory_stats())
        import jax
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("bytes_reserved", 0)
        except Exception:
            return 0

    @staticmethod
    def max_memory_reserved(device=None):
        import jax
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("peak_bytes_reserved", 0)
        except Exception:
            return 0


_PEAK_BASELINE = {"bytes": 0}


def memory_stats(device=None):
    """Full allocator statistics facade (reference
    memory/stats.h DEVICE_MEMORY_STAT / paddle.device.cuda.memory_* family).

    Merges the PJRT device allocator's stats (XLA owns device HBM — the
    reference's per-place allocator registry collapses into this single
    view) with the native host-arena counters (csrc/memory.cc) when the
    native runtime is loaded.
    """
    import jax
    out = {}
    try:
        dev = jax.devices()[0] if device is None else device
        out.update(dev.memory_stats() or {})
    except Exception:
        pass
    try:
        from ..core import native
        # probe only an ALREADY-created arena: creating one here could
        # trigger a blocking native build inside a stats query
        arena = getattr(native, "_default_arena", None)
        if arena is not None:
            in_use, peak = arena.stats()[:2]
            out["host_arena_bytes_in_use"] = in_use
            out["host_arena_peak_bytes"] = peak
    except Exception:
        pass
    return out


def reset_max_memory_allocated(device=None):
    """PJRT exposes a monotonically-tracked peak; reset is emulated by
    snapshotting the current value as the new baseline (peak queries return
    max(0, peak - baseline))."""
    import jax
    try:
        stats = jax.devices()[0].memory_stats()
        _PEAK_BASELINE["bytes"] = stats.get("peak_bytes_in_use", 0)
    except Exception:
        _PEAK_BASELINE["bytes"] = 0


def set_allocator_strategy(strategy):
    """FLAGS_allocator_strategy facade (reference
    memory/allocation/allocator_strategy.cc: naive_best_fit | auto_growth).
    XLA's client allocator is configured via env BEFORE backend init — calls
    after jax initialization raise so misuse is loud."""
    import os

    import jax
    mapping = {"auto_growth": "platform", "naive_best_fit": "bfc"}
    if strategy not in mapping:
        raise ValueError(
            f"unknown allocator strategy {strategy!r}; "
            f"expected one of {sorted(mapping)}")
    if jax._src.xla_bridge._backends:
        raise RuntimeError(
            "set_allocator_strategy must be called before the first device "
            "use (the XLA client allocator is fixed at backend init); set "
            "XLA_PYTHON_CLIENT_ALLOCATOR instead for an initialized process")
    os.environ["XLA_PYTHON_CLIENT_ALLOCATOR"] = mapping[strategy]


cuda = _DeviceNS()
tpu = _DeviceNS()
__all__ += ["memory_stats", "reset_max_memory_allocated",
            "set_allocator_strategy"]


def get_cudnn_version():
    """Reference get_cudnn_version: no cuDNN on this stack — None, matching
    the reference's CPU-only return."""
    return None


XPUPlace = TPUPlace  # accelerator aliases (reference multi-vendor places)


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_npu():
    return False


__all__ += ["get_cudnn_version", "XPUPlace", "is_compiled_with_xpu",
            "is_compiled_with_rocm", "is_compiled_with_npu"]
