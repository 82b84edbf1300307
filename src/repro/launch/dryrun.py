import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST stay first — jax locks the device count on
# first init, and the dry-run needs 512 placeholder CPU devices.

DOC = """Multi-pod dry-run: lower + compile every (architecture x input
shape) on the production meshes, record memory/cost analysis and roofline
terms.

No arrays are ever materialized — all inputs are ShapeDtypeStructs.  The
XLA_FLAGS line above MUST precede any other import (jax locks the device
count on first init); smoke tests and benchmarks do NOT import this module.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out results/dryrun.json
"""

import argparse

import dataclasses
import functools
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (BlockKind, InputShape, ModelConfig,
                                SHAPES, get_config, input_specs, list_archs)
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.models import model as M
from repro.models import sharding as S
from repro.models.param import tree_map_specs
from repro.roofline.analysis import build_report
from repro.train.optimizer import AdamWConfig, AdamWState
from repro.train.train_loop import train_step

# Sliding-window serve variant for long-context decode on pure-dense archs
# (DESIGN.md §4): window 8192 — an explicit variant, not the checkpoint
# semantics.  Archs that are already sub-quadratic run unmodified.
LONG_CONTEXT_WINDOW = 8192

# whisper-tiny x long_500k is semantically void (enc-dec audio) — skipped.
SKIPS = {("whisper-tiny", "long_500k"): "enc-dec audio; 524k-token decode "
         "of a 30s clip is semantically void (DESIGN.md §4)"}

# FSDP for serving when model-axis sharding alone leaves > ~6 GB/chip.
FSDP_SERVE_BYTES = 6 << 30


@dataclasses.dataclass
class Opts:
    """Perf-iteration knobs (§Perf hillclimbing)."""
    remat: bool = True
    impl: str = "xla"
    fsdp_serve: Optional[bool] = None     # None = auto by size
    opt_state_dtype: str = "float32"
    no_tp: bool = False                   # fold model axis into FSDP (no
                                          # Megatron activation all-reduces)
    moe_a2a: bool = False                 # seq-parallel expert-parallel a2a
    cache_dtype: Optional[str] = None     # e.g. "int8" quantized KV cache
    weight_dtype: Optional[str] = None    # e.g. "int8" weight-only quant
    microbatch: int = 1                   # gradient accumulation slices
    remat_policy: Optional[str] = None    # None=full remat | "dots"


def variant_for(cfg: ModelConfig, shape: InputShape) -> Optional[ModelConfig]:
    """Returns the config (possibly a documented variant) or None to skip."""
    if (cfg.name, shape.name) in SKIPS:
        return None
    if shape.name == "long_500k":
        kinds = set(cfg.layer_pattern)
        # natively long-context: no global-attention layers, OR chunked
        # local attention carries most layers (llama4 iRoPE: the minority
        # global layers keep a full 524k cache — B=1 decode affords it)
        subquad = (BlockKind.ATTN not in kinds) or \
            (BlockKind.CHUNKED_ATTN in kinds)
        if not subquad:
            # pure/partly global attention -> sliding-window serve variant
            pattern = tuple(BlockKind.LOCAL_ATTN if k == BlockKind.ATTN else k
                            for k in cfg.pattern)
            return dataclasses.replace(
                cfg, name=cfg.name + "-sw8k", pattern=pattern,
                window=max(cfg.window, LONG_CONTEXT_WINDOW))
    return cfg


def serve_fsdp(cfg: ModelConfig, opts: Opts) -> bool:
    if opts.fsdp_serve is not None:
        return opts.fsdp_serve
    return cfg.n_params * 2 / 16 > FSDP_SERVE_BYTES


def _abstract(specs, rules, mesh: Mesh, dtype: str):
    def mk(s):
        return jax.ShapeDtypeStruct(
            s.shape, jnp.dtype(s.dtype or dtype),
            sharding=NamedSharding(mesh, S.spec_for(s.shape, s.axes, rules,
                                                    mesh)))
    return tree_map_specs(mk, specs)


def _batch_abstract(specs: Dict[str, jax.ShapeDtypeStruct], rules, mesh):
    out = {}
    for k, v in specs.items():
        sh = S.batch_sharding(v.shape, mesh, rules)
        out[k] = jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh)
    return out


# ----------------------------------------------------------------------
# Step builders: return (jit_fn, example_args)
# ``probe`` switches to the while-free lowering used for cost analysis
# (unrolled layer loop + loop-free attention), because cost_analysis()
# counts while-loop bodies exactly once.
# ----------------------------------------------------------------------
def _wrap_rules(fn, mesh, rules):
    def wrapped(*a, **kw):
        with S.axis_rules(mesh, rules):
            return fn(*a, **kw)
    return wrapped


def build_train(cfg: ModelConfig, shape: InputShape, mesh: Mesh, opts: Opts,
                probe: bool = False):
    rules = S.rules_for("train", fsdp=True, no_tp=opts.no_tp,
                        moe_a2a=opts.moe_a2a)
    specs = M.param_specs(cfg)
    p_abs = _abstract(specs, rules, mesh, cfg.dtype)
    p_shard = jax.tree.map(lambda a: a.sharding, p_abs)
    dt = opts.opt_state_dtype
    o_abs = AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P())),
        m=jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.dtype(dt), sharding=a.sharding), p_abs),
        v=jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.dtype(dt), sharding=a.sharding), p_abs))
    o_shard = jax.tree.map(lambda a: a.sharding, o_abs)
    batch = _batch_abstract(input_specs(cfg, shape), rules, mesh)

    ocfg = AdamWConfig(state_dtype=dt)
    fn = functools.partial(train_step, cfg, ocfg, impl=opts.impl,
                           remat=opts.remat, unroll=probe,
                           microbatch=int(opts.microbatch),
                           remat_policy=opts.remat_policy)
    jit_fn = jax.jit(_wrap_rules(fn, mesh, rules),
                     in_shardings=(p_shard, o_shard, None),
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1))
    return jit_fn, (p_abs, o_abs, batch)


def build_prefill(cfg: ModelConfig, shape: InputShape, mesh: Mesh, opts: Opts,
                  probe: bool = False):
    rules = S.rules_for("serve", fsdp=serve_fsdp(cfg, opts),
                        no_tp=opts.no_tp, moe_a2a=opts.moe_a2a)
    p_abs = _abstract(M.param_specs(cfg), rules, mesh, cfg.dtype)
    p_shard = jax.tree.map(lambda a: a.sharding, p_abs)
    batch = _batch_abstract(input_specs(cfg, shape), rules, mesh)
    fn = functools.partial(M.prefill, cfg, impl=opts.impl, unroll=probe)
    jit_fn = jax.jit(_wrap_rules(fn, mesh, rules),
                     in_shardings=(p_shard, None))
    return jit_fn, (p_abs, batch)


def _quantize_abstract(p_abs, dtype_str):
    """Swap >=2-dim weight leaves to the narrow dtype (norms/bias stay)."""
    dt = jnp.dtype(dtype_str)
    return jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, dt, sharding=a.sharding)
                   if len(a.shape) >= 2 else a), p_abs)


def build_decode(cfg: ModelConfig, shape: InputShape, mesh: Mesh, opts: Opts,
                 probe: bool = False):
    rules = S.rules_for("serve", fsdp=serve_fsdp(cfg, opts),
                        no_tp=opts.no_tp, moe_a2a=opts.moe_a2a)
    p_abs = _abstract(M.param_specs(cfg), rules, mesh, cfg.dtype)
    if opts.weight_dtype:
        p_abs = _quantize_abstract(p_abs, opts.weight_dtype)
    p_shard = jax.tree.map(lambda a: a.sharding, p_abs)
    c_abs = _abstract(M.cache_specs(cfg, shape.global_batch, shape.seq_len,
                                    kv_dtype=opts.cache_dtype),
                      rules, mesh, cfg.dtype)
    c_shard = jax.tree.map(lambda a: a.sharding, c_abs)
    batch = _batch_abstract(input_specs(cfg, shape), rules, mesh)
    fn = functools.partial(M.decode_step, cfg, impl=opts.impl,
                           unroll=probe)
    # 0-layer cost probes have an empty cache -> decode returns None for it
    c_out = c_shard if jax.tree.leaves(c_abs) else None
    jit_fn = jax.jit(_wrap_rules(fn, mesh, rules),
                     in_shardings=(p_shard, c_shard, None, None),
                     out_shardings=(None, c_out), donate_argnums=(1,))
    return jit_fn, (p_abs, c_abs, batch["tokens"], batch["pos"])


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


# ----------------------------------------------------------------------
# Cost probes: lower a 0-layer and a 1-period (unrolled, loop-free) variant
# and combine linearly:  total = head + (period - head) * n_layers / P.
# Attention-like quadratics are stubbed in train/prefill probes (their
# loop-free form materializes S x S scores no flash kernel writes to HBM)
# and added back from the analytic kernel-traffic model.
# ----------------------------------------------------------------------
def _probe_cost(cfg: ModelConfig, shape: InputShape, mesh: Mesh, opts: Opts):
    from repro.roofline import analytic
    from repro.roofline.hlo import collective_bytes as _cb
    P_len = max(len(cfg.pattern), 1)
    probe_impl = "xla_full" if shape.kind == "decode" else "xla_noattn"
    probe_opts = dataclasses.replace(opts, impl=probe_impl)

    def one(n_layers: int):
        c = dataclasses.replace(cfg, n_layers=n_layers)
        jit_fn, args = BUILDERS[shape.kind](c, shape, mesh, probe_opts,
                                            probe=True)
        comp = jit_fn.lower(*args).compile()
        ca = comp.cost_analysis() or {}
        coll, per_type, counts = _cb(comp.as_text())
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
                "coll": float(coll), "per_type": per_type, "counts": counts}

    head = one(0)
    period = one(P_len)
    scale = cfg.n_layers / P_len

    def comb(a, b):
        return {k: a[k] + (b[k] - a[k]) * scale
                for k in ("flops", "bytes", "coll")}

    out = comb(head, period)
    out["per_type"] = {k: int(head["per_type"].get(k, 0) +
                              (period["per_type"].get(k, 0) -
                               head["per_type"].get(k, 0)) * scale)
                       for k in period["per_type"]}
    out["counts"] = {k: int(head["counts"].get(k, 0) +
                            (period["counts"].get(k, 0) -
                             head["counts"].get(k, 0)) * scale)
                     for k in period["counts"]}
    # sLSTM recurrence runs S sequential steps inside a while loop the
    # probes count once — add the missing (S-1) steps analytically.
    n_slstm = sum(1 for k in cfg.layer_pattern if k == BlockKind.SLSTM)
    if n_slstm and shape.kind != "decode":
        nh = cfg.n_heads
        hd = cfg.d_model // nh
        step_flops = 2 * shape.global_batch * nh * hd * 4 * hd
        mult = 3.0 if shape.kind == "train" else 1.0
        out["flops"] += (shape.seq_len - 1) * step_flops * n_slstm * mult \
            / mesh_chips(mesh)
    # add back the stubbed attention/mLSTM/RG-LRU terms from the analytic
    # kernel-traffic model (global -> per-chip by the axes that parallelize)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if probe_impl == "xla_noattn":
        par = analytic.parallel_chips(cfg, sizes.get("data", 1),
                                      sizes.get("model", 1),
                                      sizes.get("pod", 1))
        a_flops, a_bytes = analytic.stubbed_op_costs(cfg, shape)
        out["flops"] += a_flops / par
        out["bytes"] += a_bytes / par
        out["analytic_flops_per_chip"] = a_flops / par
        out["analytic_bytes_per_chip"] = a_bytes / par
    # expert-weight streaming the dense gmm proxy does not read
    out["bytes"] += analytic.moe_weight_traffic_per_chip(
        cfg, shape, sizes.get("model", 1))
    return out


# ----------------------------------------------------------------------
def run_combo(arch: str, shape_name: str, mesh_name: str,
              opts: Optional[Opts] = None, verbose: bool = True
              ) -> Dict[str, Any]:
    opts = opts or Opts()
    shape = SHAPES[shape_name]
    cfg0 = get_config(arch)
    cfg = variant_for(cfg0, shape)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "ok",
                           "opts": dataclasses.asdict(opts)}
    if cfg is None:
        rec.update(status="skip", reason=SKIPS[(arch, shape_name)])
        return rec
    if cfg.name != cfg0.name:
        rec["variant"] = cfg.name

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh_chips(mesh)
    t0 = time.time()
    try:
        # 1) full executable: proves lowering/partitioning, gives per-device
        #    memory + the real collective schedule of the deployed program.
        jit_fn, args = BUILDERS[shape.kind](cfg, shape, mesh, opts)
        lowered = jit_fn.lower(*args)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        mem = None
        if ma is not None:
            mem = (getattr(ma, "argument_size_in_bytes", 0)
                   + getattr(ma, "output_size_in_bytes", 0)
                   + getattr(ma, "temp_size_in_bytes", 0)
                   - getattr(ma, "alias_size_in_bytes", 0))
        # 2) cost probes: while-free lowerings -> true per-step FLOPs/bytes
        cost = _probe_cost(cfg, shape, mesh, opts)
        ca = {"flops": cost["flops"], "bytes accessed": cost["bytes"]}
        report = build_report(cfg, shape, mesh_name, chips, ca, "",
                              bytes_per_device=mem)
        report.coll_bytes = cost["coll"]
        report.coll_breakdown = cost["per_type"]
        report.coll_counts = cost["counts"]
        # 3) fusion-aware HBM model (primary memory term; HLO bytes kept
        #    as the unfused upper bound)
        from repro.roofline import analytic
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        fsdp = True if shape.kind == "train" else serve_fsdp(cfg, opts)
        report.model_bytes = analytic.memory_model(
            cfg, shape, sizes.get("data", 1), sizes.get("model", 1),
            sizes.get("pod", 1), fsdp=fsdp,
            opt_state_bytes=jnp.dtype(opts.opt_state_dtype).itemsize,
            weight_bytes=(jnp.dtype(opts.weight_dtype).itemsize
                          if opts.weight_dtype else 2),
            cache_bytes=(jnp.dtype(opts.cache_dtype).itemsize
                         if opts.cache_dtype else 2),
            microbatch=int(opts.microbatch))
        rec.update(
            compile_s=round(time.time() - t0, 1),
            chips=chips,
            report=report.to_dict(),
            hlo_bytes_per_device=mem,
            n_params=cfg.n_params,
            n_active_params=cfg.n_active_params,
        )
        if verbose:
            r = report
            print(f"[ok] {arch:26s} {shape_name:12s} {mesh_name:6s} "
                  f"chips={chips:3d} compile={rec['compile_s']:6.1f}s "
                  f"mem/dev={(mem or 0)/2**30:6.2f}GiB "
                  f"t_comp={r.t_compute*1e3:8.2f}ms t_mem={r.t_memory*1e3:8.2f}ms "
                  f"t_coll={r.t_collective*1e3:8.2f}ms dom={r.dominant}",
                  flush=True)
    except Exception as e:
        rec.update(status="error", error=repr(e),
                   traceback=traceback.format_exc())
        if verbose:
            print(f"[ERR] {arch} {shape_name} {mesh_name}: {e!r}", flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--opt", action="append", default=[],
                    help="perf knobs, e.g. --opt remat=false --opt impl=xla")
    args = ap.parse_args(argv)

    opts = Opts()
    for kv in args.opt:
        k, v = kv.split("=", 1)
        cur = getattr(opts, k)
        if isinstance(cur, bool) or k == "fsdp_serve":
            v = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            v = int(v)
        setattr(opts, k, v)

    archs = [a for a in list_archs() if a != "tinyyolo-v2"] \
        if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for arch in archs:
        for sh in shapes:
            for mesh_name in meshes:
                results.append(run_combo(arch, sh, mesh_name, opts))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
