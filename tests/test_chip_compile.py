"""The chip path without the chip.

* The main path's Pallas kernels, compiled at granite-3-2b's published
  widths (32/8 heads, hd 64, bf16) for a described v5e, and the paged
  ones at other head layouts and chunk sizes: the TPU compiler refuses
  here what interpret mode lets through (tile alignment, VMEM).
* The paged kernels' wrappers hand the K/V pool to the kernel as given:
  no pool-sized transpose, pad or copy.  The engine's decode program,
  compiled as the engine calls it, pins how many pool-sized copies the
  stacked pool's stored layout still costs.
* ``chip_smoke.py``: its serving phase driven on the CPU at
  ``granite-3-2b-smoke``, and its refusal to run anywhere but a TPU.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU library), never at import.
"""
import dataclasses
import functools
import importlib.util
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CFG = get_config("granite-3-2b")
SLOTS, MAX_LEN, PAGE, CHUNK = 4, 1024, 16, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _kernel_case(name: str, cfg=CFG, chunk: int = CHUNK):
    """(kernel, argument shapes) at the smoke's serving shapes."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bf16, i32 = jnp.bfloat16, jnp.int32
    pages = SLOTS * (MAX_LEN // PAGE) + 1
    pool = ((pages, PAGE, KV, hd), bf16)
    if name == "paged_decode":
        return da.paged_decode_attention, [
            ((SLOTS, 1, H, hd), bf16), pool, pool,
            ((SLOTS, MAX_LEN // PAGE), i32), ((SLOTS,), i32)]
    if name == "paged_chunk_prefill":
        return da.paged_prefill_attention, [
            ((1, chunk, H, hd), bf16), pool, pool,
            ((1, MAX_LEN // PAGE), i32), ((1,), i32), ((1,), i32)]
    if name == "flash_prefill":
        n = 300
        return functools.partial(fa.flash_attention, causal=True), [
            ((1, n, H, hd), bf16), ((1, n, KV, hd), bf16),
            ((1, n, KV, hd), bf16)]
    if name == "dense_decode":
        return da.decode_attention, [
            ((SLOTS, 1, H, hd), bf16), ((SLOTS, MAX_LEN, KV, hd), bf16),
            ((SLOTS, MAX_LEN, KV, hd), bf16), ((SLOTS,), i32)]
    raise ValueError(name)


# (kernel, architecture, chunk): the smoke's four, then wider chunks and
# other head layouts.  The chunk path holds one KV head's rows in VMEM at a
# time: with every head's rows at once, granite's 1024-token chunk runs out.
KERNEL_CASES = [(n, CFG.name, CHUNK) for n in (
    "paged_decode", "paged_chunk_prefill", "flash_prefill", "dense_decode")] + [
    ("paged_chunk_prefill", "granite-3-2b", 512),
    ("paged_chunk_prefill", "granite-3-2b", 1024),
    ("paged_decode", "qwen2.5-14b", CHUNK),
    ("paged_chunk_prefill", "qwen2.5-14b", 256),
    ("paged_decode", "deepseek-7b", CHUNK),
    ("paged_chunk_prefill", "deepseek-7b", 512)]


def _case_id(case):
    name, arch, chunk = case
    return name if (arch, chunk) == (CFG.name, CHUNK) else \
        f"{name}-{arch}-{chunk}"


@pytest.mark.parametrize("case", KERNEL_CASES, ids=_case_id)
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    name, arch, chunk = case
    fn, shapes = _kernel_case(name, get_config(arch), chunk)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


_BIG_PASS = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} (transpose|pad|copy)\(")


def _big_passes(hlo: str, elems: int) -> list:
    """Transposes, pads and copies with at least ``elems`` elements."""
    return [m.group(0) for m in _BIG_PASS.finditer(hlo)
            if math.prod(int(d) for d in m.group(1).split(",") if d) >= elems]


@pytest.mark.parametrize("name", ["paged_decode", "paged_chunk_prefill"])
def test_paged_kernel_wrappers_add_no_pool_pass(name, one_chip,
                                                no_persistent_cache):
    """The wrappers alone: given a row-major pool, nothing as large as
    the pool is transposed, padded or copied before the kernel.  The
    layout XLA stores the engine's pool in is pinned separately below."""
    from jax.experimental.layout import Format, Layout
    fn, shapes = _kernel_case(name)
    pool_shape = shapes[1][0]
    row_major = Format(Layout(major_to_minor=tuple(range(len(pool_shape)))),
                       one_chip)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=(
                row_major if i in (1, 2) else one_chip))
            for i, (s, dt) in enumerate(shapes)]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    big = _big_passes(hlo, math.prod(pool_shape))
    assert not big, big


# pool-slice-sized copies in the compiled decode step at each benchmark
# cell's engine shape (architecture, layers, slots, max_len, pool tokens,
# pinned count).  granite-3-2b: XLA stores the stacked pool pages-minor
# (head dim 64 half-fills a lane tile), so each layer copies its K and V
# slices to row-major before the kernel (2) and back after (2), and the
# step ends with a copy of each stacked pool (2).  deepseek-7b-pp: head dim
# 128 fills a lane tile and the pool is stored row-major
# ({4,3,2,1,0}), so no per-layer copy is left; the two stacked-pool copies
# at the step's end remain, 1.34 GB each.
DECODE_POOL_CASES = [
    pytest.param("granite-3-2b", 40, 4, 2048, 8192, 6, id="granite"),
    pytest.param("deepseek-7b", 10, 4, 4096, 16384, 2, id="deepseek-7b-pp"),
]


@pytest.mark.parametrize("arch,layers,slots,max_len,pool_tokens,pinned",
                         DECODE_POOL_CASES)
def test_decode_program_pool_copies_pinned(arch, layers, slots, max_len,
                                           pool_tokens, pinned, one_chip,
                                           no_persistent_cache):
    """The engine's decode program as the engine jits it (stacked pool,
    default layouts).  A change to how the pool is stored should lower
    these counts: lower the pins with it."""
    from repro.models import model as M
    from repro.models.param import abstract_params
    from repro.serve.engine import ServingEngine
    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    pages = pool_tokens // PAGE + 1

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(abstract_params(M.param_specs(cfg), cfg.dtype))
    cache = on_chip(abstract_params(
        M.paged_cache_specs(cfg, slots, max_len, pages, PAGE), cfg.dtype))
    eng = ServingEngine.__new__(ServingEngine)
    eng.cfg, eng.impl = cfg, "pallas"
    eng._paged_flags = M.paged_leaf_flags(cfg, cache)

    def arg(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    step = jax.jit(functools.partial(ServingEngine._decode_paged_impl, eng),
                   donate_argnums=(1,))
    hlo = step.lower(params, cache, arg((slots, 1)), arg((slots,)),
                     arg((slots, max_len // PAGE)),
                     arg((slots,), jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in hlo
    slice_elems = pages * PAGE * cfg.n_kv_heads * cfg.hd
    big = _big_passes(hlo, slice_elems)
    assert all(" copy(" in b for b in big), big
    assert len(big) == pinned, big


# ----------------------------------------------------------------------
# chip_smoke.py
# ----------------------------------------------------------------------
def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_serving_phase_on_cpu_smoke_config():
    cs = _chip_smoke()
    rep = cs.serve(arch="granite-3-2b-smoke")
    assert rep["cfg"].name == "granite-3-2b-smoke"
    assert rep["succeeded"] == rep["events"] == len(cs.PROMPT_LENS)
    assert rep["cold"] == [True] + [False] * (rep["events"] - 1)
    assert 0 < rep["tokens"] <= rep["events"] * cs.NEW_TOKENS
    engine = rep["engine"]
    # long prompts took the chunked path, short ones the whole prefill
    assert engine.n_prefill_chunks > 0
    assert engine.n_prefills >= rep["events"]
    assert "HloModule" in cs.decode_step_hlo(engine)
    # the kernels (interpret mode here) against xla on the same weights
    errs = cs.compare(rep["cfg"], engine.params, impl="interpret", n=40)
    for e in errs.values():
        assert e["max_abs_err"] <= cs.LOGIT_REL_TOL * e["ref_max_abs"]
        assert e["argmax_agree"]


def test_chip_smoke_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout     # no result line
