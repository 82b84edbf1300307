"""Serving launcher: generation workloads submitted through the unified
invocation gateway.

``--backend sim`` (default) drives a Hardless cluster of pods on the
discrete-event clock — real execution of ``--arch`` inside the sim, or
roofline-calibrated service times with ``--sim`` (no hardware needed).
``--arch`` names a config as published (granite-3-2b: 40 layers, d_model
2048, bf16); ``<arch>-smoke`` is its CPU-sized variant.
``--backend engine`` bypasses the cluster and executes on this host's
JAX devices directly (the gateway's engine backend).
``--cluster N`` spawns a real multi-process deployment instead — a
master process owner in this process plus N worker *processes* connected
over the cluster RPC protocol (``docs/cluster.md``); runtimes are
registered by importable spec so the workers can rebuild them; on a TPU
host each worker process owns one chip.
``--workflow N`` submits N three-step *chained* workflows instead of flat
events (each step's prompts are the previous step's generations, resolved
through the object store — the composition layer demo).

Control-plane flags (``docs/controlplane.md``) attach an SLO scaler
(``--slo-ms``), warm-pool floors (``--min-warm``) and per-tenant quotas
(``--tenant-quota NAME=RATE[:BURST]``) over either backend;
``--metrics-out PATH`` dumps the collector (Prometheus text, or JSON for
``.json`` paths) after the run.  ``--fault-spec`` (``docs/reliability.md``)
arms a fault-injection schedule — kill/stall sim nodes, crash engine
workers — and the run demonstrates at-least-once delivery: every event
still settles (redelivered within the retry bound or a permanent error
record).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b-smoke \
        --pods 2 --events 6
    PYTHONPATH=src python -m repro.launch.serve --backend engine \
        --arch granite-3-2b-smoke --workflow 2 --max-batch 4
    PYTHONPATH=src python -m repro.launch.serve --cluster 2 --events 6 \
        --arch granite-3-2b-smoke
    PYTHONPATH=src python -m repro.launch.serve --backend engine \
        --arch granite-3-2b-smoke --min-warm 1 --slo-ms 2000 \
        --tenant-quota free=2:4 --metrics-out metrics.prom
"""
from __future__ import annotations

import argparse
import atexit
import json

from repro.configs import get_config
from repro.controlplane import (AdmissionPolicy, ControlPlane,
                                ControlPlaneConfig, SLOPolicy, WarmPolicy)
from repro.core.accelerator import AcceleratorSpec
from repro.core.cluster import Cluster
from repro.faults import inject, parse_fault_spec
from repro.core.runtime import RuntimeDef, SimProfile
from repro.data.tokenizer import ByteTokenizer
from repro.gateway import (EngineBackend, Gateway, SimBackend, Workflow,
                           WorkflowStepError)
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.api import make_serve_runtime
from repro.serve.service_model import roofline_profile


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    help="comma-separated arch ids, served as published; "
                         "<arch>-smoke is the CPU-sized variant")
    ap.add_argument("--pods", type=int, default=None,
                    help="sim backend only (default 2)")
    ap.add_argument("--events", type=int, default=6)
    ap.add_argument("--max-new-tokens", type=int, default=6)
    ap.add_argument("--scheduler", default=None,
                    choices=["warm", "fifo", "cost", "hetero-latency",
                             "hetero-cost", "hetero-energy"],
                    help="sim backend only (default warm; the hetero-* "
                         "family scores placements by objective — "
                         "docs/scheduling.md)")
    ap.add_argument("--objective", default=None,
                    choices=["latency", "cost", "energy"],
                    help="placement objective (default latency): picks the "
                         "matching hetero-* scheduler on the sim backend "
                         "and steers control-plane scale-out/prewarm "
                         "toward the cheapest / most energy-frugal "
                         "accelerator type that still holds the SLO "
                         "(docs/scheduling.md)")
    ap.add_argument("--backend", default="sim", choices=["sim", "engine"],
                    help="sim = pod cluster on the event clock; "
                         "engine = direct execution on this host")
    ap.add_argument("--cluster", type=int, default=None, metavar="N",
                    help="spawn a real master/worker deployment with N "
                         "worker processes (overrides --backend; "
                         "docs/cluster.md)")
    ap.add_argument("--sim", action="store_true",
                    help="simulate --arch with roofline-derived service "
                         "times instead of real execution "
                         "(sim backend only)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="engine backend: largest micro-batch of compatible "
                         "events one jitted call may serve (default 8; "
                         "1 disables batching)")
    ap.add_argument("--batch-wait-ms", type=float, default=None,
                    help="engine backend: max wait for a micro-batch to "
                         "fill before dispatching a partial one "
                         "(default 2 ms)")
    ap.add_argument("--workflow", type=int, default=0, metavar="N",
                    help="submit N generate->refine->refine chained "
                         "workflows (one submission each) instead of "
                         "--events flat invocations")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="attach a control plane whose SLO scaler targets "
                         "this RLat p99 (milliseconds)")
    ap.add_argument("--min-warm", type=int, default=None, metavar="N",
                    help="control plane keeps N instances of every "
                         "registered runtime warm (prewarmed off the "
                         "critical path, pinned against eviction)")
    ap.add_argument("--tenant-quota", action="append", default=None,
                    metavar="NAME=RATE[:BURST]",
                    help="per-tenant admission quota in events/s (burst "
                         "defaults to 2*rate); repeatable; over-quota "
                         "events are shed as rejected")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="after the run, dump the metrics collector to "
                         "PATH — JSON for .json paths, Prometheus text "
                         "otherwise")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable invocation tracing and write the run's "
                         "span tree to PATH as Chrome/Perfetto "
                         "trace_event JSON (load in ui.perfetto.dev; "
                         "docs/observability.md)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV cache page size in tokens for real engines "
                         "(0 = dense per-slot cache, the paged engine's "
                         "differential reference; docs/architecture.md)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill prompts longer than this in chunk-sized "
                         "pieces interleaved with decode steps (0 = whole-"
                         "prompt prefill; needs --page-size > 0)")
    ap.add_argument("--tuned", action="store_true",
                    help="re-exec once with the host tuning preset "
                         "(tcmalloc LD_PRELOAD, quiet XLA logging, host "
                         "device count; launch/tuning.py) before JAX init")
    ap.add_argument("--fault-spec", default=None, metavar="JSON|@FILE",
                    help="arm a fault-injection schedule: a JSON list of "
                         "actions (or @path to a file holding one), e.g. "
                         '\'[{"at": 2.0, "op": "kill-node", "node": '
                         '"pod0"}]\'; sim ops: kill-node/stall-node, '
                         "engine ops: crash-worker, cluster ops: "
                         "kill-worker-process (docs/reliability.md)")
    args = ap.parse_args(argv)
    if args.tuned and argv is None:
        # LD_PRELOAD/XLA_FLAGS only bind at process start: apply the
        # preset by re-exec (no-op inside the already-tuned child).
        # Skipped for programmatic calls (argv given) — tests must not
        # exec away the interpreter.
        from repro.launch.tuning import maybe_reexec
        maybe_reexec("repro.launch.serve")
    enable_compile_cache()
    if args.prefill_chunk and not args.page_size:
        ap.error("--prefill-chunk needs --page-size > 0 (chunked prefill "
                 "scatters into the paged KV pool)")
    mode = "cluster" if args.cluster is not None else args.backend
    if mode == "cluster":
        if args.cluster < 1:
            ap.error("--cluster needs at least 1 worker process")
        if args.sim or args.pods is not None or args.scheduler is not None:
            ap.error("--sim/--pods/--scheduler only apply to --backend sim "
                     "(--cluster runs real worker processes)")
        if args.batch_wait_ms is not None:
            ap.error("--batch-wait-ms only applies to --backend engine "
                     "(cluster workers batch at the master's queue)")
    elif mode == "engine":
        if args.sim:
            ap.error("--sim requires --backend sim (the engine backend "
                     "executes real code)")
        if args.pods is not None or args.scheduler is not None:
            ap.error("--pods/--scheduler only apply to --backend sim "
                     "(the engine backend schedules on this host's devices)")
    elif args.max_batch is not None or args.batch_wait_ms is not None:
        ap.error("--max-batch/--batch-wait-ms only apply to "
                 "--backend engine (the sim models batching in its "
                 "service-time profiles)")
    if args.objective is not None and args.scheduler is not None:
        ap.error("--objective and --scheduler both pick the sim placement "
                 "policy; pass one (--objective X equals --scheduler "
                 "hetero-X plus the control-plane spend steer)")
    objective = args.objective if args.objective is not None else "latency"
    pods = args.pods if args.pods is not None else 2
    scheduler = args.scheduler if args.scheduler is not None else (
        f"hetero-{args.objective}" if args.objective is not None else "warm")
    max_batch = args.max_batch if args.max_batch is not None else 8

    acc_type = "v5e-4x4" if mode == "sim" else "host-jax"
    handle = None
    if mode == "cluster":
        from repro.cluster import start_cluster
        # serve runtimes jit-compile on their cold start: generous lease
        # and heartbeat bounds so compilation never reads as death
        # this process stays off JAX: each worker owns one chip
        handle = start_cluster(args.cluster, lease_s=300.0,
                               heartbeat_timeout_s=30.0,
                               max_batch=max_batch,
                               ready_timeout_s=60.0, pin_chips=True)
        gw = Gateway(handle.backend)
    elif mode == "sim":
        slice_spec = AcceleratorSpec(type=acc_type, slots=1,
                                     mem_bytes=16 << 30, cost_per_hour=19.2,
                                     chips=16)
        cluster = Cluster(scheduler=scheduler, seed=0)
        for p in range(pods):
            cluster.add_node(f"pod{p}", [slice_spec])
        gw = Gateway(SimBackend(cluster))
    else:
        gw = Gateway(EngineBackend(
            max_batch=max_batch,
            batch_wait_s=(args.batch_wait_ms / 1e3
                          if args.batch_wait_ms is not None else 0.002)))

    m = gw.metrics
    if args.trace_out:
        # tracing on before the first submit, so spans ride every event
        # from the front door; the tracer shares the backend's clock and
        # feeds per-runtime span summaries into the metrics collector
        from repro import obs
        obs.enable(clock=gw.backend.now, metrics=m)

    # fault-injection runs and Ctrl-C must not lose the snapshots: the
    # dumps run atexit AND in the finally below, once-flagged so a clean
    # exit does not write twice
    _flushed = []

    def flush_outputs():
        if _flushed:
            return
        _flushed.append(True)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                if args.metrics_out.endswith(".json"):
                    json.dump(m.to_json(), f, indent=2)
                else:
                    f.write(m.prometheus_text())
            print(f"wrote {args.metrics_out}")
        if args.trace_out:
            from repro import obs
            n = obs.export(args.trace_out)
            print(f"wrote {args.trace_out} ({n} trace events)")

    if args.metrics_out or args.trace_out:
        atexit.register(flush_outputs)

    tok = ByteTokenizer()
    prompts = [tok.encode(t) for t in
               ["the quick brown fox jumps", "hardware accelerators",
                "serverless computing is"]]
    data_ref = gw.put({"prompts": prompts})

    rt_ids = []
    for arch in args.arch.split(","):
        if mode == "cluster":
            # cluster runtimes travel as importable factory specs, never
            # as closures — each worker process rebuilds its own copy
            from repro.cluster import load_runtime_spec
            rdef = load_runtime_spec(
                "repro.cluster.runtimes:serve_runtime",
                {"arch": arch, "max_batch": max_batch,
                 "max_slots": 4, "max_len": 64,
                 "page_size": args.page_size,
                 "prefill_chunk": args.prefill_chunk})
        elif args.sim:
            cfg = get_config(arch)
            prof = roofline_profile(cfg, batch=len(prompts),
                                    new_tokens=args.max_new_tokens)
            rdef = RuntimeDef(runtime_id=f"serve-{cfg.name}",
                              profiles={acc_type: prof})
        else:
            cfg = get_config(arch)
            # engine backend: make_serve_runtime's host-jax default profile
            acc_types = None if args.backend == "engine" else \
                {acc_type: SimProfile(elat_median_s=0.4, cold_start_s=2.0)}
            # the runtime's own batch cap must track the CLI flag, or the
            # dispatcher silently clamps to make_serve_runtime's default
            rdef = make_serve_runtime(cfg, acc_types=acc_types,
                                      max_slots=4, max_len=64,
                                      max_batch=max_batch,
                                      page_size=args.page_size,
                                      prefill_chunk=args.prefill_chunk)
        rt_ids.append(gw.register(rdef))

    plane = None
    injector = None
    try:
        if args.slo_ms is not None or args.min_warm is not None or \
                args.tenant_quota:
            quotas = {}
            for spec_str in args.tenant_quota or []:
                name, _, rate_s = spec_str.partition("=")
                if not name or not rate_s:
                    ap.error(f"--tenant-quota {spec_str!r}: expected "
                             f"NAME=RATE[:BURST]")
                rate_part, _, burst_part = rate_s.partition(":")
                rate = float(rate_part)
                burst = float(burst_part) if burst_part else 2.0 * rate
                quotas[name] = (rate, burst)
            plane = ControlPlane(ControlPlaneConfig(
                tick_interval_s=5.0 if mode == "sim" else 0.5,
                objective=objective,
                # the sim's pre-provisioned pods are the capacity floor
                # (they are not drainable); engine/cluster floor at one
                slo=(SLOPolicy(slo_rlat_p99_s=args.slo_ms / 1e3,
                               min_units=pods if mode == "sim" else 1)
                     if args.slo_ms is not None else None),
                warm=(WarmPolicy(min_warm={rid: args.min_warm
                                           for rid in rt_ids})
                      if args.min_warm is not None else None),
                admission=(AdmissionPolicy(tenant_quotas=quotas)
                           if quotas else None),
            )).attach(gw.backend)
            plane.start()

        if args.fault_spec:
            spec_text = args.fault_spec
            if spec_text.startswith("@"):
                with open(spec_text[1:]) as f:
                    spec_text = f.read()
            injector = inject(gw.backend, parse_fault_spec(spec_text))

        cfg_run = {"max_new_tokens": args.max_new_tokens}
        if args.workflow:
            # composition demo: each workflow is a 3-step chain whose
            # steps round-robin over the registered arch runtimes; step
            # i+1's prompts are step i's generations, fetched from the
            # object store
            wf_futs = []
            for w in range(args.workflow):
                wf = Workflow(f"chain{w}")
                prev = wf.step("generate", rt_ids[w % len(rt_ids)],
                               data_ref=data_ref, config=cfg_run)
                for j, stage in enumerate(("refine", "polish")):
                    prev = wf.step(stage,
                                   rt_ids[(w + j + 1) % len(rt_ids)],
                                   after=prev, config=cfg_run, retries=1)
                wf_futs.append(gw.submit_workflow(wf))
            wf_ok = True
            for fut in wf_futs:
                try:
                    fut.result()
                except WorkflowStepError as e:
                    print(f"  workflow {fut.name} FAILED: {e}")
                    wf_ok = False
                print(f"  workflow {fut.name}: {fut.statuses()}")
                wf_ok &= all(s == "done"
                             for s in fut.statuses().values())
        else:
            for i in range(args.events):
                gw.invoke(rt_ids[i % len(rt_ids)], data_ref=data_ref,
                          config=cfg_run, at=0.5 * i)
            gw.drain()

        ok = sum(i.success for i in m.completed)
        print(f"[{gw.backend.name}] {ok}/{len(m.completed)} events "
              f"succeeded")
        for inv in m.completed:
            print(f"  ev{inv.inv_id} rt={inv.runtime_id:28s} "
                  f"acc={inv.accelerator} cold={int(inv.cold_start)} "
                  f"ELat={inv.elat:.3f}s RLat={inv.rlat:.3f}s")
        if mode == "sim":
            for node in gw.backend.cluster.nodes:
                print(f"{node.name}: cold={node.n_cold_starts} "
                      f"warm={node.n_warm_starts}")
        elif mode == "cluster":
            st = gw.backend.stats()
            for name, rep in sorted(st.get("workers", {}).items()):
                ws = rep.get("stats") or {}
                print(f"{name}: pid={ws.get('pid')} "
                      f"batches={ws.get('n_batches', 0)} "
                      f"cold={ws.get('n_cold_starts', 0)} "
                      f"warm={ws.get('n_warm_starts', 0)} "
                      f"settled={ws.get('n_settled', 0)}")
            print(f"master: settled={st.get('settled')} "
                  f"requeued={st.get('requeued')} "
                  f"workers_lost={st.get('workers_lost')} "
                  f"duplicate_settles={st.get('duplicate_settles')}")
        else:
            eb = gw.backend
            sizes = eb.batch_sizes or [0]
            print(f"local: cold={eb.n_cold_starts} "
                  f"warm={eb.n_warm_starts} "
                  f"prewarmed={eb.n_prewarms} batches={eb.n_batches} "
                  f"max_batch_served={max(sizes)} "
                  f"rejected={eb.n_rejected}")
        if plane is not None:
            plane.stop()
            print(f"controlplane: {plane.summary()}")
        if injector is not None:
            injector.disarm()
            s = m.summary()
            print(f"faults: {injector.summary()} "
                  f"retried={s['retried']:.0f} "
                  f"failed={s['failed']:.0f} "
                  f"exhausted={s['retries_exhausted']:.0f}")
    finally:
        # faults/Ctrl-C must not lose the snapshots: flush before
        # teardown (the atexit hook is the once-flagged second line
        # of defense)
        flush_outputs()
        if handle is not None:
            handle.close()  # shutdown master, reap worker processes
    if args.workflow:
        # a retried-then-recovered step leaves its failed attempt in the
        # metrics; the demo's verdict is whether the workflows completed
        return 0 if wf_ok else 1
    # admission sheds are deliberate policy outcomes, not failures; with
    # faults armed, a retry-exhausted error record is the at-least-once
    # contract working as designed (settled, not stranded)
    n_shed = sum(1 for i in m.completed if i.rejected)
    n_exhausted = (sum(1 for i in m.completed if i.retries_exhausted)
                   if injector is not None else 0)
    return 0 if ok + n_shed + n_exhausted == len(m.completed) else 1


if __name__ == "__main__":
    raise SystemExit(main())
