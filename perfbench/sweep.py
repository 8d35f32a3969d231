#!/usr/bin/env python3
"""Find a query cell's knee: the highest offered rate it sustains.

    python3 perfbench/sweep.py --workload ucr-serve.hot --seed 7 \\
        --seconds 20 --rates 2,4,6,8

One process sets the cell up once and runs one window per rate, in
ascending order, on the cell's own traffic with only the rate changed. Each
window prints one JSON line: the rate, the median and 95th percentile of
due-to-answer latency, the requests answered, and how long after the close
the last answer came (a backlog that grows through the window shows there).
The knee is read from these lines by hand and recorded in PERF.md; the
traffic file then fixes a rate below it. Needs the chip, like run.py.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from bench import serve, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if run.prepare(cell) != 0:
        return 1
    rates = [float(r) for r in args.rates.split(",")]
    sess = serve.Session(cell, args.seed)
    for i, rate in enumerate(rates):
        win = sess.measure(args.seconds, dict(cell["traffic"], rate_per_s=rate), stream=100 + i)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(win["lat"]), "answered": win["answered"],
            "p50_ms": serve.percentile_ms(win["lat"], 50),
            "p95_ms": serve.percentile_ms(win["lat"], 95),
            "tail_after_close_s": win["end"] - win["close"],
            "late_max_ms": float(win["late"].max() * 1e3),
        }), flush=True)
    sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
