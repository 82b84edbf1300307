"""The chip path without the chip.

* The main path's Pallas kernels, compiled at granite-3-2b's published
  widths (32/8 heads, hd 64, bf16) for a described v5e: the TPU compiler
  refuses here what interpret mode lets through (tile alignment, VMEM).
* ``chip_smoke.py``: its serving phase driven on the CPU at
  ``granite-3-2b-smoke``, and its refusal to run anywhere but a TPU.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU library), never at import.
"""
import functools
import importlib.util
import os
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


def _kernel_case(name: str):
    """(kernel, argument shapes) at the smoke's serving shapes."""
    H, KV, hd = CFG.n_heads, CFG.n_kv_heads, CFG.hd
    bf16, i32 = jnp.bfloat16, jnp.int32
    pages = SLOTS * (MAX_LEN // PAGE) + 1
    pool = ((pages, PAGE, KV, hd), bf16)
    if name == "paged_decode":
        return da.paged_decode_attention, [
            ((SLOTS, 1, H, hd), bf16), pool, pool,
            ((SLOTS, MAX_LEN // PAGE), i32), ((SLOTS,), i32)]
    if name == "paged_chunk_prefill":
        return da.paged_prefill_attention, [
            ((1, CHUNK, H, hd), bf16), pool, pool,
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


@pytest.mark.parametrize("name", ["paged_decode", "paged_chunk_prefill",
                                  "flash_prefill", "dense_decode"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


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
