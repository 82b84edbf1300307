"""End-to-end behaviour: the Hardless control plane executing REAL JAX
model serving as runtime instances (cold start = jit + weights), plus
metrics plumbing."""
import os
import subprocess
import sys

from repro.configs import get_config
from repro.core.cluster import Cluster
from repro.core.accelerator import AcceleratorSpec
from repro.core.events import Invocation
from repro.core.runtime import SimProfile
from repro.serve.api import make_serve_runtime

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def make_cluster():
    cl = Cluster(scheduler="warm", seed=0)
    cpu_slice = AcceleratorSpec(type="cpu-slice", slots=1,
                                mem_bytes=4 << 30, cost_per_hour=0.2)
    cl.add_node("pod0", [cpu_slice])
    cfg = get_config("granite-3-2b").reduced()
    rdef = make_serve_runtime(
        cfg, acc_types={"cpu-slice": SimProfile(elat_median_s=0.5,
                                                cold_start_s=1.0)},
        max_slots=2, max_len=48)
    cl.register_runtime(rdef)
    return cl, rdef


def test_serverless_serving_end_to_end():
    cl, rdef = make_cluster()
    data_ref = cl.store.put({"prompts": [[1, 5, 9], [1, 7, 2]]})
    for i in range(3):
        cl.submit(Invocation(runtime_id=rdef.runtime_id, data_ref=data_ref,
                             config={"max_new_tokens": 4},
                             r_start=float(i)))
    cl.run(until=10_000.0)
    m = cl.metrics
    assert len(m.completed) == 3
    assert all(i.success for i in m.completed), \
        [(i.error) for i in m.completed]
    assert all(i.check_monotone() for i in m.completed)
    # results are persisted in object storage
    for inv in m.completed:
        res = cl.store.get_outcome(inv.result_ref)["value"]
        assert len(res["outputs"]) == 2
        assert all(len(o) <= 4 for o in res["outputs"])
    # warm reuse: only the first event cold-starts
    node = cl.nodes[0]
    assert node.n_cold_starts == 1
    assert node.n_warm_starts == 2


def test_real_execution_elat_measured():
    cl, rdef = make_cluster()
    data_ref = cl.store.put({"prompts": [[1, 2, 3]]})
    cl.submit(Invocation(runtime_id=rdef.runtime_id, data_ref=data_ref,
                         config={"max_new_tokens": 2}, r_start=0.0))
    cl.run(until=10_000.0)
    inv = cl.metrics.completed[0]
    assert inv.elat is not None and inv.elat > 0
    assert inv.rlat >= inv.elat


def _cache_probe(env_dir, tmp_path):
    """Run the entry-point helper in a fresh process, compile once, and
    report the directory used plus the entries under ``env_dir``."""
    code = ("import os, sys, jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == path\n"
            "assert jax.config.jax_compilation_cache_dir == path\n"
            "if len(sys.argv) > 1:\n"
            "    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "print(path)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    argv = [sys.executable, "-c", code]
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        argv.append("compile")
    out = subprocess.run(argv, env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_placed_from_outside_or_fixed_in_checkout(tmp_path):
    placed = tmp_path / "placed"
    assert _cache_probe(placed, tmp_path) == str(placed)
    assert any(placed.iterdir())            # entries land there
    assert sorted(p.name for p in tmp_path.iterdir()) == ["placed"]
    # unset: a fixed path inside the checkout, whatever the cwd
    root = os.path.dirname(SRC)
    assert _cache_probe(None, tmp_path) == os.path.join(root, ".jax_cache")
