#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 chipbench/calibrate.py --workload granite.chat --seconds 20 \
        --seeds 11,12,13 --control-seeds 14,15,16

One process runs the benchmark's own ``run.measure`` once per seed, with a
short window of the cell's own traffic: for ``--seeds`` the program is
judged as in every run; for ``--control-seeds`` the control stands in the
program's place (``run.compare``): at the same prompts and served tokens,
the token that the fp8 forward ranks first.  One JSON line per seed: the
verdict ``correct`` and each number compared beside the cell's limit.
The limit in ``cells/<workload>.json`` lies between the largest program
reading and the smallest control reading (see PERF.md); the benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.enable_cache()
    found = run.find_device(spec.entry["chips"])
    if found is None:
        return 3
    runs = [(int(s), False) for s in args.seeds.split(",") if s]
    runs += [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        line, notes = run.measure(spec, seed, args.seconds, False, *found,
                                  t_start=time.monotonic(), control=control)
        print(json.dumps({"seed": seed, "control": control,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"], "notes": notes[-3:]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
