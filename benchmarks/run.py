"""Benchmark harness — one entry per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH``
additionally writes the rows plus each section's raw result dict as
machine-readable JSON (the ``BENCH_*.json`` perf-trajectory format CI's
bench-smoke job records and gates on).

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --only gateway \
        --json BENCH_gateway.json
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any, Callable, Dict, List, Tuple

_ROWS: List[Dict[str, Any]] = []


def _row(name: str, us: float, derived: str) -> None:
    _ROWS.append({"name": name, "us_per_call": round(us, 1),
                  "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


def _sec_scaling() -> Dict[str, Any]:
    # --- Fig 3 / Fig 4: scaling workload, dual-GPU vs all accelerators ---
    from benchmarks.bench_scaling import bench as scaling_bench
    t0 = time.perf_counter()
    s = scaling_bench(scale=1.0)
    us = (time.perf_counter() - t0) * 1e6 / 2
    _row("fig3_dual_gpu_rfast_max", us,
         f"rfast_max={s['fig3_dual_gpu']['rfast_max']:.2f}/s")
    _row("fig4_all_accel_rfast_max", us,
         f"rfast_max={s['fig4_all_accel']['rfast_max']:.2f}/s")
    _row("fig4_minus_fig3_delta_rfast", us,
         f"max_delta={s['delta_rfast']['max']:.2f}/s "
         f"p1_mean_delta={s['delta_rfast']['p1_mean']:.2f}/s "
         f"(VPU capacity 0.63/s; paper quotes ~+0.75)")
    _row("fig3_p1_rfast_mean", us,
         f"{s['fig3_dual_gpu']['rfast_p1_mean']:.2f}/s "
         f"(capacity 4/1.675=2.39/s)")
    _row("fig4_p1_rfast_mean", us,
         f"{s['fig4_all_accel']['rfast_p1_mean']:.2f}/s "
         f"(capacity 2.39+0.63=3.02/s)")
    _row("c3_rlat_max_dual_gpu", us,
         f"rlat_max={s['c3_dual_gpu']['rlat_max']:.1f}s (120s timeout)")
    _row("c3_rlat_max_all_accel", us,
         f"rlat_max={s['c3_all_accel']['rlat_max']:.1f}s "
         f"(paper claim C3: higher than dual-gpu)")
    return s


def _sec_elat() -> Dict[str, Any]:
    # --- §V.B ELat medians ---------------------------------------------
    from benchmarks.bench_elat import bench as elat_bench
    t0 = time.perf_counter()
    e = elat_bench()
    us = (time.perf_counter() - t0) * 1e6
    _row("elat_median_gpu", us,
         f"{e['median_elat_gpu_s']*1e3:.0f}ms (paper 1675ms)")
    _row("elat_median_vpu", us,
         f"{e['median_elat_vpu_s']*1e3:.0f}ms (paper 1577ms)")
    return e


def _sec_scheduler() -> Dict[str, Any]:
    # --- beyond paper: scheduler ablation -------------------------------
    from benchmarks.bench_scheduler import bench as sched_bench
    t0 = time.perf_counter()
    p = sched_bench()
    us = (time.perf_counter() - t0) * 1e6 / 3
    for pol, r in p.items():
        _row(f"scheduler_{pol}", us,
             f"cold={r['cold_starts']} p50={r['rlat_p50']:.2f}s "
             f"p99={r['rlat_p99']:.2f}s cost=${r['cost_usd']:.3f}")
    return p


def _sec_elasticity() -> Dict[str, Any]:
    # --- beyond paper: elasticity (autoscaler) --------------------------
    from benchmarks.bench_elasticity import bench as elas_bench
    t0 = time.perf_counter()
    el = elas_bench()
    us = (time.perf_counter() - t0) * 1e6 / 2
    for name, r in el.items():
        _row(f"elasticity_{name}", us,
             f"p50={r['rlat_p50']:.2f}s p99={r['rlat_p99']:.2f}s "
             f"node_s={r['node_seconds']:.0f}")
    return el


def _sec_gateway() -> Dict[str, Any]:
    # --- gateway: sim policies + engine serial-vs-batched ---------------
    from benchmarks.bench_gateway import bench as gw_bench
    t0 = time.perf_counter()
    g = gw_bench(real=True)
    us = (time.perf_counter() - t0) * 1e6 / max(len(g), 1)
    for name, r in g.items():
        if "throughput_per_s" in r:
            _row(f"gateway_{name.replace('/', '_')}", us,
                 f"elat_p50={r['elat_p50_s']:.2f}s "
                 f"rlat_p50={r['rlat_p50_s']:.2f}s "
                 f"cold={r['cold_starts']} "
                 f"tput={r['throughput_per_s']:.2f}/s")
    _row("gateway_engine_speedup", us,
         f"batched_vs_serial="
         f"{g['engine/speedup']['batched_vs_serial_speedup']:.2f}x")
    return g


def _sec_workflow() -> Dict[str, Any]:
    # --- workflow composition: sim DAGs + live engine chains ------------
    from benchmarks.bench_workflow import bench as wf_bench
    t0 = time.perf_counter()
    w = wf_bench(real=True)
    us = (time.perf_counter() - t0) * 1e6 / max(len(w), 1)
    s = w["sim/pipeline"]
    _row("workflow_sim_pipeline", us,
         f"steps={s['n_steps']} makespan={s['makespan_s']:.2f}s "
         f"steps_per_s={s['steps_per_s']:.2f}")
    e = w["engine/chains"]
    _row("workflow_engine_chains", us,
         f"steps={e['n_steps']} mean_batch={e['mean_batch']:.1f} "
         f"steps_per_s={e['steps_per_s']:.2f}")
    return w


def _sec_coldstart() -> Dict[str, Any]:
    # --- control plane: cold vs warm vs prewarmed invoke latency --------
    from benchmarks.bench_coldstart import bench as cs_bench
    t0 = time.perf_counter()
    c = cs_bench(real=True)
    us = (time.perf_counter() - t0) * 1e6 / max(len(c), 1)
    life = c["sim/lifecycle"]
    _row("coldstart_sim_lifecycle", us,
         f"cold={life['cold_rlat_s']:.2f}s warm={life['warm_rlat_s']:.2f}s "
         f"ratio={life['cold_to_warm_rlat_ratio']:.2f}x")
    pre = c["sim/prewarm"]
    _row("coldstart_sim_prewarm", us,
         f"warm_fraction={pre['warm_fraction']:.2f} "
         f"cold_starts={pre['cold_starts']} (min_warm=1)")
    if "engine/speedup" in c:
        _row("coldstart_engine_prewarm_speedup", us,
             f"first_invoke="
             f"{c['engine/speedup']['prewarmed_first_invoke_speedup']:.1f}x "
             f"(prewarmed vs cold)")
    return c


def _sec_controlplane() -> Dict[str, Any]:
    # --- control plane: SLO scaler vs queue pressure, tenant quotas -----
    from benchmarks.bench_controlplane import bench as cp_bench
    t0 = time.perf_counter()
    p = cp_bench()
    us = (time.perf_counter() - t0) * 1e6 / max(len(p), 1)
    for name in ("queue_pressure", "slo"):
        r = p[f"sim/{name}"]
        _row(f"controlplane_{name}", us,
             f"p99={r['rlat_p99_s']:.1f}s slo={r['slo_p99_s']:.0f}s "
             f"holds={int(r['holds_slo'])} node_s={r['node_seconds']:.0f}")
    t = p["sim/tenants"]
    _row("controlplane_tenants", us,
         f"free={t['free_served']}/{t['free_offered']} "
         f"(shed {t['free_shed']}) paid={t['paid_served']} "
         f"(shed {t['paid_shed']})")
    return p


def _sec_faults() -> Dict[str, Any]:
    # --- reliability: goodput under fault schedules vs no-retry ---------
    from benchmarks.bench_faults import bench as faults_bench
    t0 = time.perf_counter()
    f = faults_bench(real=True)
    us = (time.perf_counter() - t0) * 1e6 / max(len(f), 1)
    k = f["sim/node_kill"]
    _row("faults_sim_node_kill", us,
         f"goodput={k['goodput']}/{k['submitted']} "
         f"(noretry {k['goodput_noretry']}) retried={k['retried']} "
         f"all_settled={int(k['all_settled'])}")
    if "engine/worker_crash" in f:
        e = f["engine/worker_crash"]
        _row("faults_engine_worker_crash", us,
             f"goodput={e['goodput']}/{e['submitted']} "
             f"crashes={e['worker_crashes']} retried={e['retried']} "
             f"all_settled={int(e['all_settled'])}")
        w = f["workflow/resume"]
        _row("faults_workflow_resume", us,
             f"parent_reruns={w['parent_reruns']} "
             f"failed_step_runs={w['failed_step_runs']} "
             f"only_failed_rerun={int(w['only_failed_rerun'])}")
    return f


def _sec_serving() -> Dict[str, Any]:
    # --- serving engine: paged KV vs dense at equal budget (real JAX) ---
    from benchmarks.bench_serving import bench as serving_bench
    t0 = time.perf_counter()
    v = serving_bench()
    _ = (time.perf_counter() - t0) * 1e6
    _row("serving_engine_reduced", v["us_per_decode_step"],
         f"tokens_per_s={v['tokens_per_s']:.1f}")
    s = v["speedup"]
    _row("serving_paged_vs_dense", v["paged"]["wall_s"] * 1e6,
         f"paged={v['paged']['decode_tokens_per_s']:.0f}tok/s "
         f"dense={v['dense']['decode_tokens_per_s']:.0f}tok/s "
         f"speedup={s['decode_tokens_per_s']:.2f}x "
         f"ttft_long={s['ttft_long']:.2f}x "
         f"ttft_short={s['ttft_short']:.2f}x "
         f"roofline_frac={v['paged']['roofline_fraction']:.3f}")
    return v


def _sec_roofline() -> Dict[str, Any]:
    # --- roofline table (from the dry-run sweep, if present) ------------
    from benchmarks.bench_roofline import bench as roof_bench
    t0 = time.perf_counter()
    r = roof_bench()
    us = (time.perf_counter() - t0) * 1e6
    if "error" in r:
        _row("roofline_sweep", us, r["error"])
    else:
        c = r["counts"]
        _row("roofline_sweep", us,
             f"ok={c['ok']} skip={c['skip']} err={c['error']} "
             f"dominant={r['dominant_histogram']}")
        for arch, shape, frac in r["worst_roofline_fraction"]:
            _row(f"roofline_worst_{arch}_{shape}", us, f"fraction={frac}")
    return r


def _sec_scale() -> Dict[str, Any]:
    # --- indexed core at scale: 1M events (BENCH_SCALE_N to reduce) -----
    from benchmarks.bench_scale import bench as scale_bench
    t0 = time.perf_counter()
    s = scale_bench()
    us = (time.perf_counter() - t0) * 1e6 / max(s["n"], 1)
    _row("scale_events", us,
         f"n={s['n']} settled={s['settled']} wall={s['wall_s']:.1f}s "
         f"rate={s['events_per_s']:.0f}/s rss={s['peak_rss_mb']:.0f}MB")
    _row("scale_verdicts", us,
         f"all_settled={int(s['all_settled'])} "
         f"wall_ok={int(s['within_wall_ceiling'])} "
         f"rss_ok={int(s['within_rss_ceiling'])} "
         f"quantile_ok={int(s['quantile_bound_ok'])} "
         f"(rank_err={s['quantile_rank_err_max']:.4f})")
    return s


def _sec_cluster() -> Dict[str, Any]:
    # --- multi-process master/worker deployment (docs/cluster.md) ------
    from benchmarks.bench_cluster import bench as cluster_bench
    t0 = time.perf_counter()
    c = cluster_bench()
    us = (time.perf_counter() - t0) * 1e6 / 2
    s = c["scaling"]
    _row("cluster_scaling_speedup", us,
         f"4w={s['w4']['events_per_s']:.0f}/s "
         f"1w={s['w1']['events_per_s']:.0f}/s "
         f"speedup={s['speedup_4w_vs_1w']:.2f}x "
         f"(acceptance floor 2x)")
    k = c["sigkill"]
    _row("cluster_sigkill_goodput", us,
         f"goodput={k['goodput']}/{k['submitted']} "
         f"workers_lost={k['workers_lost']} requeued={k['requeued']} "
         f"all_settled={int(k['all_settled'])}")
    return c


def _sec_tracing() -> Dict[str, Any]:
    # --- observability cost + span completeness (docs/observability.md)
    from benchmarks.bench_tracing import bench as tracing_bench
    t0 = time.perf_counter()
    tr = tracing_bench()
    us = (time.perf_counter() - t0) * 1e6 / 2
    o = tr["engine/overhead"]
    _row("tracing_engine_overhead", us,
         f"on={o['wall_on_s']:.3f}s off={o['wall_off_s']:.3f}s "
         f"ratio={o['enabled_over_disabled']:.3f} "
         f"ok={int(o['overhead_ok'])} (ceiling 1.05)")
    c = tr["engine/completeness"]
    _row("tracing_span_completeness", us,
         f"settled={c['settled']} closed_roots={c['closed_roots']} "
         f"complete={int(c['span_complete'])}")
    s = tr["sim/overhead"]
    _row("tracing_sim_overhead", us,
         f"ratio={s['enabled_over_disabled']:.3f} (informational)")
    return tr


def _sec_hetero() -> Dict[str, Any]:
    # --- heterogeneous placement: objective frontier + data locality ----
    from benchmarks.bench_hetero import bench as hetero_bench
    t0 = time.perf_counter()
    h = hetero_bench(real=True)
    us = (time.perf_counter() - t0) * 1e6 / max(len(h), 1)
    for obj in ("latency", "cost", "energy"):
        r = h[f"sim/{obj}"]
        _row(f"hetero_{obj}", us,
             f"p99={r['rlat_p99_s']:.1f}s holds={int(r['holds_slo'])} "
             f"fleet=${r['fleet_cost_usd']:.3f} "
             f"energy={r['energy_joules']:.0f}J "
             f"by_type={r['invocations_by_type']}")
    fr = h["sim/frontier"]
    _row("hetero_frontier", us,
         f"cost_cut={fr['cost_cut_fraction']:.2f} "
         f"(gate >=0.20) energy_cut={fr['energy_cut_fraction']:.2f} "
         f"holds_slo_all={int(fr['holds_slo_all'])} "
         f"cost_cut_ok={int(fr['cost_cut_ok'])}")
    lo = h["sim/locality"]
    _row("hetero_locality", us,
         f"rate={lo['locality_rate']:.2f} "
         f"hits={lo['locality_hits']}/{lo['eligible_steps']} "
         f"store_gets={lo['store_gets_delta']} "
         f"ok={int(lo['locality_ok'])} (floor 0.8)")
    ag = h["cluster/agreement"]
    _row("hetero_agreement", us,
         f"sim={ag['sim_hits']}/{ag['eligible']} "
         f"cluster={ag['cluster_hits']}/{ag['eligible']} "
         f"agreement_ok={int(ag['agreement_ok'])}")
    return h


SECTIONS: List[Tuple[str, Callable[[], Dict[str, Any]]]] = [
    ("scaling", _sec_scaling),
    ("elat", _sec_elat),
    ("scheduler", _sec_scheduler),
    ("elasticity", _sec_elasticity),
    ("gateway", _sec_gateway),
    ("workflow", _sec_workflow),
    ("coldstart", _sec_coldstart),
    ("controlplane", _sec_controlplane),
    ("faults", _sec_faults),
    ("cluster", _sec_cluster),
    ("hetero", _sec_hetero),
    ("serving", _sec_serving),
    ("roofline", _sec_roofline),
    ("scale", _sec_scale),
    ("tracing", _sec_tracing),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only sections whose name contains one of "
                         "these comma-separated substrings "
                         f"(of: {[n for n, _ in SECTIONS]})")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + per-section raw results as "
                         "JSON (e.g. BENCH_gateway.json)")
    args = ap.parse_args(argv)

    tokens = args.only.split(",") if args.only else None
    if tokens is not None:
        # every token must name at least one section — a typo'd token
        # silently running nothing (or only the other tokens' sections)
        # is how perf gates rot
        unknown = [t for t in tokens
                   if not any(t and t in n for n, _ in SECTIONS)]
        if unknown:
            ap.error(f"--only: unknown section(s) {unknown} "
                     f"(valid: {[n for n, _ in SECTIONS]})")
    picked = [(n, f) for n, f in SECTIONS
              if tokens is None or any(t and t in n for t in tokens)]
    if not picked:
        ap.error(f"--only {args.only!r} matches no section "
                 f"(have: {[n for n, _ in SECTIONS]})")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    _ROWS.clear()               # fresh trajectory per in-process run
    print("name,us_per_call,derived")
    results: Dict[str, Any] = {}
    for name, fn in picked:
        results[name] = fn()

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"sections": results, "rows": _ROWS}, f, indent=2,
                      default=str)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
