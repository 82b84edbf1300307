#!/usr/bin/env python3
"""Where the chip's idle time goes in a cell's traced window.

    python3 chipbench/idle_split.py --workload granite.chat --seconds 51 \
        --seeds 11,12,13

Per seed, one window traced as ``run.py --trace 1`` traces it (the span
tracer and the JAX profiler on, the same warm-up before), then the device
trace put on the tracer's clock (``tracer_clock.py``).  One JSON line per
seed: the end-to-end metrics of the traced run, the cell's per-layer
metrics, window and busy seconds, the idle seconds by cause and the idle
gaps of ``idle_causes.LONG_S`` and more while a request was outstanding
(``idle_causes.py``), the clock offset, ``idle_pending_share`` computed
directly from the idle intervals beside its reader's value, the trace's
size and the seconds its reduction took.  The served tokens are not
compared with the reference here: ``run.py`` judges correctness.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402
import idle_causes  # noqa: E402
import run  # noqa: E402
import stats  # noqa: E402
import tracer_clock  # noqa: E402
from layer_metrics._intervals import intersect, length, pending  # noqa: E402


def traced_window(spec, seed: int, seconds: float, peak) -> dict:
    import gc

    import jax
    from repro import obs
    t_start = time.monotonic()
    sched = harness.schedule(spec, seed, seconds)
    weights = harness.reference_module(spec).make_weights(spec.model, seed)
    served = harness.Served(spec, weights, seed, sched[0].max_new_tokens)
    harness.warm_up(served, sched, seed)
    counter = harness.CompileCounter()
    obs.reset()
    obs.enable(clock=served.backend.now)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    counter.on = True
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        done, t0_mono, t0_backend = harness.window(served, sched, seconds)
    counter.on = False
    jax.profiler.stop_trace()
    obs.disable()
    setup_s = t0_mono - t_start
    served.close()
    del served, weights
    gc.collect()

    t_red = time.monotonic()
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    trace = devtrace.reduce_planes(planes)
    reduce_s = time.monotonic() - t_red
    offset = tracer_clock.clock_offset_s(planes)
    trace["clock_offset_s"] = offset
    trace["idle_intervals"] = tracer_clock.idle_intervals(planes, offset)
    clock_s = time.monotonic() - t_red - reduce_s
    size = devtrace.size_bytes(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    spans = [s.to_record() for s in obs.TRACER.spans()]
    obs.reset()

    record = {"spec": spec, "peaks": peak, "requests": done, "spans": spans,
              "trace": trace, "compiles_in_window": counter.n}
    finished = [r for r in done if r["ok"]]
    rlat = [(r["r_end"] - r["due"]) if r["ok"] else math.inf for r in done]
    last = max((r["r_end"] for r in finished), default=t0_backend)
    tokens = sum(len(r["output"]) for r in finished)
    when = pending(record)
    direct = None if when is None or offset is None else \
        100.0 * length(intersect(trace["idle_intervals"], when)) / \
        trace["window_s"]
    return {
        "seed": seed,
        "end_to_end": {
            "setup_s": setup_s,
            "rlat_p50_s": stats.percentile(rlat, 50),
            "rlat_mean_s": sum(rlat) / len(rlat),
            "tokens_per_s": tokens / max(last - t0_backend, 1e-9)},
        "failed": len(done) - len(finished),
        "per_layer": {k: v["value"]
                      for k, v in run.layer_metrics(spec, record).items()},
        "window_s": trace["window_s"], "busy_s": trace["busy_s"],
        "idle": idle_causes.split(record),
        "idle_pending_share_direct": direct,
        "clock_offset_s": offset,
        "n_idle_intervals": len(trace["idle_intervals"]),
        "n_spans": len(spans),
        "trace_bytes": size, "reduce_s": reduce_s, "clock_s": clock_s,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.enable_cache()
    found = run.find_device(spec.entry["chips"])
    if found is None:
        return 3
    for s in args.seeds.split(","):
        print(json.dumps(traced_window(spec, int(s), args.seconds,
                                       found[1])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
