#!/usr/bin/env python3
"""Read a query cell's correctness numbers for the program and its control.

    python3 perfbench/readings.py --workload ucr-serve.hot --seconds 10 \\
        --seeds 11,12,13

For each seed, in one process: the cell's set-up and one window at its own
load, then the numbers its runs compare, read twice on the same sampled
answers: once for the program's, once with the control (``bench.control``:
the reference at the next precision down) in the program's place, each
judged against the cell's limits as a run judges them. Each seed prints one
JSON line. The lower reading of a number is the largest the program gives
over a dozen seeds or more; the upper, the smallest the control gives; the
limit in ``perfbench/limits/<config>.json`` lies between them. Needs the
chip.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from bench import control, serve, spec


def control_knn(r, x, rows):
    return control.knn_high(control.transform32(x, r.result.v, r.result.mean), rows)


def judged(cell: dict, checked: dict) -> dict:
    """``checked`` with the verdict a run would give its numbers."""
    numbers = {k: checked[k] for k in ("tlb_worst_map_se", "knn_excess")}
    limits = dict(cell["limits"], tlb_worst_map_se=checked["tlb_limit_se"])
    checks = run.compare(numbers, limits)
    return dict(checked, correct=all(c["ok"] for c in checks))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if run.prepare(cell) != 0:
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        sess = serve.Session(cell, seed)
        win = sess.measure(args.seconds)
        sess.close()
        picks = serve.checked_sample(win, win["lat"], int(sess.traffic["checked_requests"]), seed)
        pairs = int(sess.traffic["check_pairs"])
        prog = serve.check_answers(sess.tenants, win, picks, sess.cfg, seed, pairs)
        ctrl = serve.check_answers(sess.tenants, win, picks, sess.cfg, seed, pairs,
                                   knn_answer=control_knn)
        print(json.dumps({"seed": seed, "program": judged(cell, prog),
                          "control": judged(cell, ctrl)}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
