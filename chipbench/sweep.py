#!/usr/bin/env python3
"""Find a cell's knee once: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload granite.chat --seconds 51 --rates 0.3,0.4,0.5

One process builds the cell's warm instance once and then, for each rate,
warms up the new prompt lengths and offers one window of the cell's mix at
that rate.  For each rate it prints, as a JSON line: requests due, those
finished, how long after the window closed the last one finished, the
median, mean and 90th percentile of their latency, the mean latency of
the first and last third of the window, and how many requests due in it
had not started when it closed.  A backlog that grows through the window
shows as a last third far slower than the first, requests still queued
at the close, and a drain that lasts past one batch.  The knee goes into
``cells/<workload>.json`` as a fixed rate; the benchmark itself never
searches for one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402
import run  # noqa: E402
import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.enable_cache()
    if run.find_device(spec.entry["chips"]) is None:
        return 3
    rates = [float(r) for r in args.rates.split(",")]
    first = harness.schedule(spec, args.seed, args.seconds, rates[0])
    weights = harness.reference_module(spec).make_weights(spec.model,
                                                          args.seed)
    served = harness.Served(spec, weights, args.seed,
                            first[0].max_new_tokens)
    try:
        for rate in rates:
            sched = harness.schedule(spec, args.seed, args.seconds, rate)
            harness.warm_up(served, sched, args.seed)
            done, _, t0b = harness.window(served, sched, args.seconds)
            ok = [r for r in done if r["ok"]]
            rl = [r["r_end"] - r["due"] for r in ok]
            third = max(len(rl) // 3, 1)
            print(json.dumps({
                "rate": rate, "due": len(done), "finished": len(ok),
                "drain_s": (max(r["r_end"] for r in ok) - t0b - args.seconds
                            if ok else None),
                "queued_at_close": sum(r["e_start"] is None or
                                       r["e_start"] > t0b + args.seconds
                                       for r in done),
                "rlat_p50_s": stats.percentile(rl, 50) if rl else None,
                "rlat_mean_s": sum(rl) / len(rl) if rl else None,
                "rlat_p90_s": stats.percentile(rl, 90) if rl else None,
                "first_third_mean_s": sum(rl[:third]) / third if rl else None,
                "last_third_mean_s": sum(rl[-third:]) / third if rl else None,
                "batches": served.backend.batch_sizes[-len(done):],
            }), flush=True)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
