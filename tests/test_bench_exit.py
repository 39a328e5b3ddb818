"""bench.py hides no failure: a lane that raises fails the exit code, and an
accelerator whose peak is unknown is an error, not a missing MFU."""
import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_bench_state(monkeypatch):
    for name in ("_LANE_ERRORS", "_LAST_CURVE", "_LAST_BREAKDOWN",
                 "_LAST_CKPT_STALL", "_LAST_COMPILED", "_LAST_LANES"):
        monkeypatch.setattr(bench, name, type(getattr(bench, name))())


def _boom():
    raise RuntimeError("lane exploded")


def test_named_lane_that_raises_exits_nonzero(monkeypatch, capsys):
    monkeypatch.setitem(bench._BENCHES, "boom", _boom)
    monkeypatch.setenv("BENCH_MODEL", "boom")
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "bench_error" and "exploded" in line["error"]


def test_extra_lane_that_raises_is_reported_and_fails(monkeypatch, capsys):
    ok = {"metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
          "mfu": None, "params": 1}
    monkeypatch.delenv("BENCH_MODEL", raising=False)
    monkeypatch.setattr(bench, "bench_bert", lambda **kw: dict(ok))
    monkeypatch.setattr(bench, "bench_resnet50", _boom)
    monkeypatch.setattr(bench, "bench_gpt", lambda **kw: dict(ok))
    monkeypatch.setattr(bench, "_bench_compiled_speedup", lambda: None)
    monkeypatch.setattr(bench, "_release_bench_state", lambda: None)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "exploded" in line["extra"]["resnet50_error"]
    # the lanes after the failed one still ran and reported
    assert line["extra"]["gpt_tokens_per_sec_per_chip"] == 1.0
    assert line["extra"]["ernie_vs_baseline"] == 1.0


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", None),
    ("tpu", "TPU v5 lite", 197e12),
])
def test_known_devices_have_a_peak(monkeypatch, platform, kind, want):
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "1")   # no longer read
    assert bench._chip_peak_flops() == want


def test_unknown_accelerator_kind_is_an_error(monkeypatch):
    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    with pytest.raises(RuntimeError, match="TPU v99"):
        bench._chip_peak_flops()
