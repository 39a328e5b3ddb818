"""Per-op microbench harness (tools/op_bench.py) — VERDICT r4 missing #1.
Reference precedent: operators/benchmark/op_tester.cc +
tools/check_op_benchmark_result.py."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import op_bench  # noqa: E402


def _doc(flash_bwd_ms=10.0, device="cpu"):
    return {"device": device, "ops": [
        {"op": "flash_attention", "dtype": "bf16", "direction": "fwd_bwd",
         "shape": "s", "fused_ms": flash_bwd_ms, "unfused_ms": 20.0,
         "speedup": 2.0},
        {"op": "fused_ffn", "dtype": "bf16", "direction": "fwd",
         "shape": "s", "fused_ms": 1.0, "unfused_ms": 1.5, "speedup": 1.5},
    ]}


class TestCheckAgainst:
    def test_clean_pass(self):
        assert op_bench.check_against(_doc(), _doc()) == []

    def test_kernel_slowdown_detected(self):
        # new doc is first arg: 12ms vs old 10ms = 20% slower > 10% tol
        regs = op_bench.check_against(_doc(12.0), _doc(10.0))
        assert len(regs) == 1
        assert regs[0]["op"] == "flash_attention"
        assert regs[0]["ratio"] == pytest.approx(1.2)

    def test_within_tolerance(self):
        assert op_bench.check_against(_doc(10.5), _doc(10.0)) == []

    def test_different_device_not_comparable(self):
        assert op_bench.check_against(_doc(99.0, device="TPU v5e"),
                                      _doc(10.0, device="cpu")) == []

    def test_shape_change_not_compared(self):
        new = _doc(99.0)
        new["ops"][0]["shape"] = "different"
        assert op_bench.check_against(new, _doc(10.0)) == []


def test_cli_small_run_and_check(tmp_path):
    """End-to-end: --small run emits the artifact; a doctored slower old
    artifact makes --check-against exit 0 (new faster), a doctored faster
    one makes it exit 1."""
    out = tmp_path / "OPBENCH.json"
    p = subprocess.run(
        [sys.executable, str(REPO / "tools/op_bench.py"), "--small",
         "--dtypes", "f32", "--iters", "1", "--inner", "1",
         "--filter", "fused_ffn", "--out", str(out)],
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = json.loads(out.read_text())
    assert len(doc["ops"]) == 2  # fwd + fwd_bwd
    for row in doc["ops"]:
        assert row["fused_ms"] > 0 and row["unfused_ms"] > 0

    # old artifact with absurdly fast fused_ms -> regression flagged
    fast = dict(doc, ops=[dict(r, fused_ms=r["fused_ms"] / 100)
                          for r in doc["ops"]])
    old = tmp_path / "OLD.json"
    old.write_text(json.dumps(fast))
    p = subprocess.run(
        [sys.executable, str(REPO / "tools/op_bench.py"), "--small",
         "--dtypes", "f32", "--iters", "1", "--inner", "1",
         "--filter", "fused_ffn", "--out", str(out),
         "--check-against", str(old)],
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 1
    report = json.loads(p.stdout.strip().splitlines()[-1])
    assert report["status"] == "fail" and report["regressions"]

class TestOpbenchDiff:
    """tools/opbench_diff.py — the kernel-tier CI gate (ISSUE 5)."""

    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, str(REPO / "tools/opbench_diff.py"), *map(str, argv)],
            capture_output=True, text=True, timeout=240)

    def test_checked_in_artifact_passes(self):
        # with no older artifact to compare with, the gate reads the rows
        # and finds nothing to fail
        p = self._run(REPO / "OPBENCH.json")
        assert p.returncode == 0, p.stdout + p.stderr
        report = json.loads(p.stdout)
        assert report["status"] == "ok" and report["regressions"] == []
        assert report["rows"] >= 16

    def test_regression_vs_old_fails(self, tmp_path):
        doc = json.loads((REPO / "OPBENCH.json").read_text())
        fast = dict(doc, ops=[dict(r, fused_ms=r["fused_ms"] / 100)
                              for r in doc["ops"]])
        old = tmp_path / "OLD.json"
        old.write_text(json.dumps(fast))
        p = self._run(REPO / "OPBENCH.json", old)
        assert p.returncode == 1
        report = json.loads(p.stdout)
        assert report["status"] == "fail" and report["regressions"]


def test_cli_smoke_mode_records_policy(tmp_path):
    """--smoke: CI-sized one-iteration sweep; rows carry both sides' times
    and their ratio, no column of a choice, and the artifact passes its own
    gate."""
    out = tmp_path / "SMOKE.json"
    p = subprocess.run(
        [sys.executable, str(REPO / "tools/op_bench.py"), "--smoke",
         "--dtypes", "f32", "--filter", "fused_ffn", "--out", str(out)],
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = json.loads(out.read_text())
    assert doc["smoke"] is True
    assert len(doc["ops"]) == 2
    for row in doc["ops"]:
        assert row["fused_ms"] > 0 and row["unfused_ms"] > 0
        assert row["speedup"] == pytest.approx(
            row["unfused_ms"] / row["fused_ms"], rel=0.01, abs=2e-3)
        assert not {"policy_choice", "chosen_ms", "effective_speedup"} & set(row)
    p = subprocess.run(
        [sys.executable, str(REPO / "tools/opbench_diff.py"), str(out)],
        capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stdout + p.stderr
