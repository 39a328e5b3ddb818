#!/usr/bin/env python
"""Kernel-tier CI gate over OPBENCH.json artifacts (ISSUE 5 satellite).

NEW against OLD: any per-op fused_ms slowdown beyond --tol (default 10%) on
the same (op, dtype, direction, shape, device) fails the run (rc != 0), via
op_bench.check_against. Which side of a row the program runs is not in the
artifact: the ops run what their names say (docs/kernels.md, "Which kernel
runs").

Usage:
    python tools/opbench_diff.py NEW.json [OLD.json] [--tol 0.10]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("new", help="OPBENCH.json to gate")
    ap.add_argument("old", nargs="?", default=None,
                    help="previous artifact for the regression check")
    ap.add_argument("--tol", type=float, default=0.10)
    ns = ap.parse_args(argv)

    with open(ns.new) as f:
        new_doc = json.load(f)

    regressions = []
    if ns.old:
        import op_bench
        with open(ns.old) as f:
            old_doc = json.load(f)
        regressions = op_bench.check_against(new_doc, old_doc, ns.tol)

    print(json.dumps({
        "status": "fail" if regressions else "ok",
        "rows": len(new_doc.get("ops", [])),
        "regressions": regressions,
    }, indent=2))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
