#!/usr/bin/env python3
"""The benchmark of record: one cell, one seed, one measured window.

    python3 chipbench/run.py --workload granite.chat --seed 7 --seconds 51 --trace 0

One process loads, warms up, measures and prints one JSON object as the
last line of standard output:

* ``--trace 0``: the cell's end-to-end metrics: ``setup_s`` (process start
  to the first due request), ``rlat_p50_s`` and ``rlat_mean_s`` (the exact
  median and the mean over every request due in the window, each timed
  from its due time to its result; a request that failed or never came
  counts as infinite) and ``tokens_per_s`` (output tokens of the window's
  requests over the time from the window's start to the last of them
  finishing);
* ``--trace 1``: the same run with the repository's span tracer and the
  JAX profiler on, and the cell's per-layer metrics, each read by
  ``layer_metrics/<name>.py`` from the run's record.

``correct`` holds when every request due in the window finished, and the
served tokens of a sample of them, drawn from the seed, lie within the
cell's limit of the float32 reference's best logit at every position
(``references/``).  On a host whose first JAX device is not a chip named
in ``peaks.py``, or with fewer chips than the cell asks for, it exits with
status 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import stats  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_device(chips: int):
    """(first JAX device, its peaks), or None where it is not a known chip
    or there are fewer than ``chips``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s) ({devs[0].device_kind})")
        return None
    try:
        return devs[0], peaks.peaks_for(devs[0].device_kind)
    except KeyError as e:
        log(str(e))
        return None


def compare(spec, seed: int, finished, control: bool = False):
    """(widest gap of a served token below the reference's best logit,
    tokens compared) over the sample of finished requests; the weights
    are made anew from the seed.

    ``control`` puts the reference's lower-precision forward in the
    program's place: at the same prompts and served tokens, the gap of
    the token that it ranks first."""
    import numpy as np
    ref = harness.reference_module(spec)
    sample = harness.pick_compared(finished, seed,
                                   spec.engine["prefill_chunk"])
    if not sample:
        return math.inf, 0
    weights = ref.make_weights(spec.model, seed)
    gaps = ref.served_gaps(spec.model, weights,
                           [r["prompt"] for r in sample],
                           [r["output"] for r in sample], control=control)
    return float(np.nanmax(gaps)), sum(len(r["output"]) for r in sample)


def layer_metrics(spec, record):
    """Every per-layer metric of this cell that its reader finds."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    out = {}
    for m in bench["per_layer"]:
        if spec.name not in m.get("workloads", [spec.name]):
            continue
        value = importlib.import_module(
            f"layer_metrics.{m['name']}").read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(spec, seed: int, seconds: float, traced: bool, dev, peak,
            t_start: float = T_START, control: bool = False):
    """One run of ``spec``: the result line (a dict) and the log lines.
    With ``control`` the verdict judges the control in the program's
    place (``compare``); the benchmark's own runs never set it."""
    import jax
    from repro import obs

    sched = harness.schedule(spec, seed, seconds)
    max_new = sched[0].max_new_tokens
    weights = harness.reference_module(spec).make_weights(spec.model, seed)
    served = harness.Served(spec, weights, seed, max_new)
    n_warm = harness.warm_up(served, sched, seed)
    counter = harness.CompileCounter()
    notes = [f"warm-up: {n_warm} requests"]

    tdir = None
    if traced:
        obs.enable(clock=served.backend.now)
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    counter.on = True
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        done, t0_mono, t0_backend = harness.window(served, sched, seconds)
    counter.on = False
    if traced:
        jax.profiler.stop_trace()
        obs.disable()
    setup_s = t0_mono - t_start
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    engine_stats = served.engine.stats()
    served.close()
    del served, weights
    gc.collect()

    finished = [r for r in done if r["ok"]]
    failed = len(done) - len(finished)
    rlat = [(r["r_end"] - r["due"]) if r["ok"] else math.inf for r in done]
    last = max((r["r_end"] for r in finished), default=t0_backend)
    tokens = sum(len(r["output"]) for r in finished)
    late = sorted(r["late_s"] for r in done)

    record = None
    if traced:
        t_red = time.monotonic()
        reduction = devtrace.reduce_dir(tdir)
        notes.append(f"trace: {devtrace.size_bytes(tdir)} bytes, reduced in "
                     f"{time.monotonic() - t_red} s")
        shutil.rmtree(tdir, ignore_errors=True)
        record = {"spec": spec, "peaks": peak, "requests": done,
                  "spans": [s.to_record() for s in obs.TRACER.spans()],
                  "trace": reduction, "compiles_in_window": counter.n}

    limit = spec.cell["limits"]["max_logit_gap"]
    gap, n_cmp = compare(spec, seed, finished, control)
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "unfinished": {"value": failed, "limit": 0}}
    correct = gap <= limit and failed == 0

    def finite(x: float) -> float:
        return x if math.isfinite(x) else 1e9

    if traced:
        metrics = layer_metrics(spec, record)
    else:
        metrics = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "rlat_p50_s": {"value": finite(stats.percentile(rlat, 50)),
                           "unit": "s"},
            "rlat_mean_s": {"value": finite(sum(rlat) / len(rlat)),
                            "unit": "s"},
            "tokens_per_s": {"value": tokens / max(last - t0_backend, 1e-9),
                             "unit": "tokens/s"},
        }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    line = {"correct": bool(correct), "attempted": len(done),
            "failed": failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = record["trace"]["breakdown"]
    line["checks"] = checks
    eos = sum(len(r["output"]) < max_new for r in finished)
    notes += [
        f"setup_s {setup_s}; requests due {len(done)}, finished "
        f"{len(finished)}, ended on EOS before {max_new} tokens {eos}; "
        f"compiles in window {counter.n}; last finished "
        f"{last - t0_backend} s after the window opened",
        f"generator late p50 {stats.percentile(late, 50)} s, "
        f"max {late[-1]} s; engine {engine_stats}",
        f"compared {n_cmp} served tokens of "
        f"{min(len(finished), harness.N_COMPARED)} requests"
        + (", the fp8 control in the program's place" if control else "")]
    notes += [f"check {k}: {c['value']} (limit {c['limit']})"
              for k, c in checks.items()]
    return line, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec(args.workload)
    harness.enable_cache()
    found = find_device(spec.entry["chips"])
    if found is None:
        return 3
    line, notes = measure(spec, args.seed, args.seconds, bool(args.trace),
                          *found)
    for n in notes:
        log(n)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
