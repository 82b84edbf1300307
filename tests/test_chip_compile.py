"""The chip path without the chip.

* The main path's Pallas kernels, compiled at granite-3-2b's published
  widths (32/8 heads, hd 64, bf16) for a described v5e, and the paged
  ones at other head layouts and chunk sizes: the TPU compiler refuses
  here what interpret mode lets through (tile alignment, VMEM).
* The paged kernels' wrappers hand the K/V pool to the kernel as given:
  no pool-sized transpose, pad or copy.  The engine's decode and chunk
  programs, compiled as the engine calls them, move no pool: the stacked
  pool is written in place and read where it lies.
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
SLOTS, MAX_LEN, PAGE, CHUNK, LAYERS = 4, 1024, 16, 256, 2


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
    pool = ((LAYERS, pages, PAGE, KV * hd), bf16)
    layer = ((), i32)
    if name == "paged_decode":
        return da.paged_decode_attention, [
            ((SLOTS, 1, H, hd), bf16), pool, pool,
            ((SLOTS, MAX_LEN // PAGE), i32), ((SLOTS,), i32), layer]
    if name == "paged_chunk_prefill":
        return da.paged_prefill_attention, [
            ((1, chunk, H, hd), bf16), pool, pool,
            ((1, MAX_LEN // PAGE), i32), ((1,), i32), ((1,), i32), layer]
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
    one layer's pool is transposed, padded or copied before the kernel.
    The layout XLA stores the engine's pool in is pinned separately
    below."""
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
    big = _big_passes(hlo, math.prod(pool_shape[1:]))
    assert not big, big


# Every instruction, fused or not, that moves pool data: a copy,
# transpose, pad, dynamic-slice or dynamic-update-slice whose shape holds
# the pool's page count and at least one layer's pool elements.  Entry
# parameters and the in-place scatters of the new rows are not counted.
_POOL_PASS = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} "
                        r"(copy|transpose|pad|dynamic-slice|"
                        r"dynamic-update-slice)\(")

# Each benchmark cell's engine shape (architecture, layers, slots,
# max_len, pool tokens, prefill chunk).  The engine's decode and chunk
# programs carry the stacked pools (n_layers, num_pages, page, KV * hd)
# through the layer scan: each layer scatters its new rows in place and
# the paged kernel reads its pages out of the stack by (layer, page).
# The TPU stores that shape row-major at head dim 64 (granite-3-2b) and
# 128 (deepseek-7b-pp) alike, so no layer's pool is re-laid out, sliced
# out, written back or copied at the step's end.
DECODE_POOL_CASES = [
    pytest.param("granite-3-2b", 40, 4, 2048, 8192, 256, id="granite"),
    pytest.param("deepseek-7b", 10, 4, 4096, 16384, 512,
                 id="deepseek-7b-pp"),
]


def _engine_program(program, arch, layers, slots, max_len, pool_tokens,
                    chunk, one_chip):
    """The engine's ``program`` compiled as the engine jits it (stacked
    pool, default layouts, cache donated), with the pool's page count and
    one layer's pool elements."""
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

    P = max_len // PAGE
    if program == "decode":
        fn, rest = ServingEngine._decode_paged_impl, (
            arg((slots, 1)), arg((slots,)), arg((slots, P)),
            arg((slots,), jnp.bool_))
    else:
        fn, rest = ServingEngine._chunk_batch_impl, (
            arg((1, chunk)), arg(()), arg((1, P)), arg((1,)))
    step = jax.jit(functools.partial(fn, eng), donate_argnums=(1,))
    compiled = step.lower(params, cache, *rest).compile()
    return compiled, pages, pages * PAGE * cfg.n_kv_heads * cfg.hd


def _pool_passes(hlo: str, pages: int, layer_elems: int) -> list:
    out = []
    for m in _POOL_PASS.finditer(hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if pages in dims and math.prod(dims) >= layer_elems:
            out.append(m.group(0))
    return out


def _assert_moves_no_pool(hlo: str, pages: int, layer_elems: int):
    assert "tpu_custom_call" in hlo
    # the paged kernel's first operand is int32 (kv_len): the benchmark's
    # trace reader names the paged kernels by it
    assert re.search(r'custom_call_target="tpu_custom_call", '
                     r'operand_layout_constraints=\{s32\[', hlo)
    big = _pool_passes(hlo, pages, layer_elems)
    assert not big, big


@pytest.mark.parametrize("arch,layers,slots,max_len,pool_tokens,chunk",
                         DECODE_POOL_CASES)
def test_decode_program_pool_copies_pinned(arch, layers, slots, max_len,
                                           pool_tokens, chunk, one_chip,
                                           no_persistent_cache):
    """The decode step moves no pool, and needs less scratch memory than
    one layer's K pool (the parent's needed 0.84 GB on granite and 3.23
    GB on deepseek-7b-pp for the stacked pools it rebuilt)."""
    compiled, pages, layer_elems = _engine_program(
        "decode", arch, layers, slots, max_len, pool_tokens, chunk,
        one_chip)
    _assert_moves_no_pool(compiled.as_text(), pages, layer_elems)
    layer_bytes = layer_elems * jnp.dtype(jnp.bfloat16).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("arch,layers,slots,max_len,pool_tokens,chunk",
                         DECODE_POOL_CASES)
def test_chunk_program_pool_copies_pinned(arch, layers, slots, max_len,
                                          pool_tokens, chunk, one_chip,
                                          no_persistent_cache):
    """A prefill chunk at the cell's chunk size moves no pool either."""
    compiled, pages, layer_elems = _engine_program(
        "chunk", arch, layers, slots, max_len, pool_tokens, chunk,
        one_chip)
    _assert_moves_no_pool(compiled.as_text(), pages, layer_elems)


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
