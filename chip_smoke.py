#!/usr/bin/env python3
"""Chip smoke: granite-3-2b at its published widths, served on a TPU.

    python chip_smoke.py              # one chip, one process
    python chip_smoke.py --chips 4    # four worker processes, a chip each

One chip drives the main path through its normal entry points: client ->
``Gateway`` -> ``EngineBackend`` -> warm serve runtime -> ``ServingEngine``
(paged KV, chunked prefill) -> Pallas kernels.  Eight events of mixed
prompt length (16 to 700 byte tokens, so both whole and chunked prefill
run) each generate 32 tokens, the first cold and the rest warm.  Then the
Pallas path is held to ``impl="xla"`` on the same weights: last-position
prefill logits, one chunked-prefill chunk, and one paged decode step.

``--chips 4`` runs only the cluster path: the master sits in this
process, which never initialises JAX, and four worker processes each own
one chip and serve the same configuration.  Every event must succeed, the
workers must report four distinct chips with one device each, and greedy
tokens for a fixed prompt set must agree across all four.

Weights are random, drawn from ``--seed``.  Where JAX finds no TPU the
script exits non-zero and prints no result.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "granite-3-2b"
ENGINE = {"page_size": 16, "prefill_chunk": 256, "max_slots": 4,
          "max_len": 1024}
# prompts longer than prefill_chunk prefill in chunks, the rest whole
PROMPT_LENS = (16, 700, 64, 300, 128, 520, 33, 256)
NEW_TOKENS = 32
CLUSTER_PROMPT_LENS = (16, 300, 700)
COMPARE_LEN = 300
# Pallas vs xla, both bf16 with f32 accumulation inside attention: they
# differ by bf16 roundings that 40 residual layers carry to the logits.
# Bound: 2^-4 of the reference's largest |logit| (agreement to 4
# significant bits); a kernel that reads the wrong keys is off by O(1).
LOGIT_REL_TOL = 2.0 ** -4


def say(msg: str) -> None:
    print(msg, flush=True)


def make_prompts(lens):
    """Byte-tokenized prompts of exactly the given lengths."""
    from repro.data.tokenizer import ByteTokenizer
    text = ("Serverless platforms share accelerators among many functions; "
            "each invocation pays for a warm instance or a cold start. ")
    ids = ByteTokenizer().encode(text * (max(lens) // len(text) + 2))
    return [ids[:n] for n in lens]


# ----------------------------------------------------------------------
# one chip: serve through the gateway
# ----------------------------------------------------------------------
def serve(arch: str = ARCH, lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
          seed: int = 0, timeout_s: float = 900.0):
    """Register the serve runtime on ``Gateway(EngineBackend())`` and
    invoke one event per prompt: the first alone (cold), the rest at once
    (warm).  Returns a report dict plus the warm engine."""
    import jax
    from repro.configs import get_config
    from repro.gateway import EngineBackend, Gateway
    from repro.serve.api import make_serve_runtime

    cfg = get_config(arch)
    rdef = make_serve_runtime(cfg, seed=seed, **ENGINE)
    setup, timing = rdef.setup, {}

    def timed_setup():
        t0 = time.perf_counter()
        engine = setup()
        jax.block_until_ready(engine.params)
        timing["weights_s"] = time.perf_counter() - t0
        return engine

    rdef.setup = timed_setup
    backend = EngineBackend()
    gw = Gateway(backend)
    try:
        rid = gw.register(rdef)
        run = {"max_new_tokens": new_tokens}
        prompts = make_prompts(lens)
        t0 = time.perf_counter()
        futs = [gw.invoke(rid, {"prompts": [prompts[0]]}, config=run)]
        futs[0].result(extra_time_s=timeout_s)
        cold_s = time.perf_counter() - t0
        futs += [gw.invoke(rid, {"prompts": [p]}, config=run)
                 for p in prompts[1:]]
        outputs = []
        for f in futs:
            try:
                outputs.append(f.result(extra_time_s=timeout_s)["outputs"][0])
            except Exception as e:  # noqa: BLE001 — counted as failed
                say(f"event {f.invocation.inv_id} failed: {e!r}")
                outputs.append(None)
        invs = [f.invocation for f in futs]
        engine = backend.handle(invs[0].runtime_key)
    finally:
        backend.shutdown()
    ok = [o is not None and 1 <= len(o) <= new_tokens and
          all(0 <= t < cfg.padded_vocab for t in o) for o in outputs]
    return {
        "cfg": cfg, "engine": engine, "outputs": outputs,
        "events": len(futs), "succeeded": sum(ok),
        "cold": [bool(i.cold_start) for i in invs],
        "tokens": sum(len(o) for o in outputs if o),
        "weights_s": timing.get("weights_s"), "cold_event_s": cold_s,
        "warm_rlat_s": [i.rlat for i in invs[1:]],
    }


def decode_step_hlo(engine) -> str:
    """Compiled HLO of the engine's paged decode step at the widest
    block table the smoke's prompts use."""
    import jax
    import jax.numpy as jnp
    B, P = engine.max_slots, engine.pages_per_seq
    spec = jax.ShapeDtypeStruct
    return engine._decode_paged.lower(
        engine.params, engine.cache, spec((B, 1), jnp.int32),
        spec((B,), jnp.int32), spec((B, P), jnp.int32),
        spec((B,), jnp.bool_)).compile().as_text()


def compare(cfg, params, impl: str = "pallas", n: int = COMPARE_LEN):
    """Max |logits(impl) - logits(xla)| for whole prefill, one paged
    prefill chunk, and one paged decode step, on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M

    tokens = jnp.asarray(make_prompts([n])[0], jnp.int32)[None]
    page = ENGINE["page_size"]
    n_pages = -(-(n + 1) // page)
    tables = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    pool = M.init_paged_cache(cfg, 1, n + 1, n_pages + 1, page)

    def run(impl_):
        pre = jax.jit(functools.partial(M.prefill, cfg, impl=impl_))
        chunk = jax.jit(functools.partial(M.prefill_chunk, cfg, impl=impl_))
        dec = jax.jit(functools.partial(M.decode_step, cfg, impl=impl_))
        lp, _ = pre(params, {"tokens": tokens})
        lc, cache = chunk(params, pool, tokens, jnp.int32(0), tables)
        nxt = jnp.argmax(lc[:, -1], axis=-1).astype(jnp.int32)[:, None]
        ld, _ = dec(params, cache, nxt, jnp.full((1,), n, jnp.int32),
                    block_tables=tables)
        return [np.asarray(x[0, -1], np.float32) for x in (lp, lc, ld)]

    got, ref = run(impl), run("xla")
    out = {}
    for name, g, r in zip(("prefill", "chunk", "decode"), got, ref):
        out[name] = {"max_abs_err": float(np.max(np.abs(g - r))),
                     "ref_max_abs": float(np.max(np.abs(r))),
                     "argmax_agree": bool(np.argmax(g) == np.argmax(r))}
    return out


def one_chip(seed: int) -> int:
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.models.model import param_specs
    from repro.models.param import param_bytes
    from repro.configs import get_config
    say(f"device_kind={dev.device_kind} count={len(jax.devices())}")
    cfg = get_config(ARCH)
    say(f"config={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} hd={cfg.hd} "
        f"dtype={cfg.dtype} "
        f"param_bytes={param_bytes(param_specs(cfg), cfg.dtype)}")
    say(f"compile_cache={cache_dir}")

    failures = []
    rep = serve(seed=seed)
    say(f"cold_start_s={rep['cold_event_s']:.3f} (weights "
        f"{rep['weights_s']} s, then compile + first event)")
    say(f"events_succeeded={rep['succeeded']}/{rep['events']} "
        f"cold={sum(rep['cold'])} warm={rep['cold'].count(False)} "
        f"tokens_generated={rep['tokens']}")
    say(f"warm_rlat_s={[round(x, 3) for x in rep['warm_rlat_s']]}")
    if rep["succeeded"] != rep["events"]:
        failures.append("not every event succeeded")
    if rep["cold"] != [True] + [False] * (rep["events"] - 1):
        failures.append(f"expected 1 cold then warm, got {rep['cold']}")

    custom = "tpu_custom_call" in decode_step_hlo(rep["engine"])
    say(f"decode_step_tpu_custom_call={custom}")
    if not custom:
        failures.append("decode step holds no Pallas kernel")

    errs = compare(rep["cfg"], rep["engine"].params)
    for name, e in errs.items():
        bound = LOGIT_REL_TOL * e["ref_max_abs"]
        say(f"logits_{name}: pallas_vs_xla_max_abs_err="
            f"{e['max_abs_err']:.5f} bound={bound:.5f} "
            f"(ref max |logit| {e['ref_max_abs']:.4f}) "
            f"argmax_agree={e['argmax_agree']}")
        if not e["max_abs_err"] <= bound:
            failures.append(f"{name} logits off by {e['max_abs_err']}")

    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    for f in failures:
        say(f"FAIL: {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


# ----------------------------------------------------------------------
# four chips: one worker process per chip, parent off JAX
# ----------------------------------------------------------------------
def cluster(n_chips: int, seed: int, timeout_s: float = 900.0) -> int:
    from repro.cluster import load_runtime_spec, start_cluster
    from repro.cluster.backend import host_tpu_chips
    from repro.gateway import Gateway
    from repro.launch.compile_cache import enable_compile_cache

    have = host_tpu_chips()
    if have < n_chips:
        print(f"chip_smoke: needs {n_chips} TPU chips, this host has "
              f"{have}", file=sys.stderr)
        return 2
    say(f"compile_cache={enable_compile_cache()}")
    failures = []
    t0 = time.perf_counter()
    handle = start_cluster(n_chips, lease_s=3600.0,
                           heartbeat_timeout_s=600.0, max_batch=1,
                           ready_timeout_s=120.0, pin_chips=True)
    try:
        gw = Gateway(handle.backend)
        rid = gw.register(load_runtime_spec(
            "repro.cluster.runtimes:serve_runtime",
            {"arch": ARCH, "max_batch": 1, "seed": seed, **ENGINE}))
        data = gw.put({"prompts": make_prompts(CLUSTER_PROMPT_LENS)})
        run = {"max_new_tokens": NEW_TOKENS}
        results, served_by = [], set()
        # each worker takes one event while the others are busy with
        # their cold start; a later round covers any worker left out
        for _ in range(3):
            futs = [gw.invoke(rid, data_ref=data, config=run)
                    for _ in range(n_chips)]
            for f in futs:
                try:
                    results.append(f.result(extra_time_s=timeout_s))
                except Exception as e:  # noqa: BLE001
                    failures.append(f"event failed: {e!r}")
                served_by.add(f.invocation.node)
            if len(served_by) >= n_chips or failures:
                break
        say(f"events_succeeded={len(results)}/"
            f"{len(results) + len(failures)} served_by={sorted(served_by)} "
            f"wall_s={time.perf_counter() - t0:.3f}")
        devices = {}
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            workers = handle.backend.stats().get("workers", {})
            devices = {name: (w.get("stats") or {}).get("device")
                       for name, w in workers.items()}
            if len(devices) >= n_chips and all(devices.values()):
                break
            time.sleep(0.5)
    finally:
        handle.close()

    for name, d in sorted(devices.items()):
        say(f"{name}: device={d}")
    reports = [d for d in devices.values() if d]
    chips = {d["visible_chips"] for d in reports}
    if len(reports) != n_chips or len(chips) != n_chips:
        failures.append(f"expected {n_chips} distinct chips, got {chips}")
    if any(d["platform"] != "tpu" or d["count"] != 1 for d in reports):
        failures.append("a worker saw other than one TPU device")
    if len(served_by) < n_chips:
        failures.append(f"only {sorted(served_by)} served")
    outputs = [r["outputs"] for r in results]
    same = bool(outputs) and all(o == outputs[0] for o in outputs)
    say(f"greedy_tokens_identical_across_workers={same} "
        f"(events={len(outputs)}, prompts={list(CLUSTER_PROMPT_LENS)})")
    if not same:
        failures.append("greedy tokens differ across workers")
    from jax._src import xla_bridge
    parent_on_jax = xla_bridge.backends_are_initialized()
    say(f"parent_initialised_jax={parent_on_jax}")
    if parent_on_jax:
        failures.append("the parent process initialised a JAX backend")
    for f in failures:
        say(f"FAIL: {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": reports[0]["platform"], "kind": reports[0]["kind"],
        "count": len(chips)}}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the one-worker-per-chip cluster path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args(argv)
    if args.chips == 1:
        return one_chip(args.seed)
    return cluster(args.chips, args.seed)


if __name__ == "__main__":
    raise SystemExit(main())
