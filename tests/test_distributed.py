"""Distribution correctness tests that need >1 XLA device.

The device count is process-global (and the main pytest process must keep
1 device for the smoke tests), so these run in subprocesses with
``--xla_force_host_platform_device_count`` set.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_a2a_matches_local_routing():
    run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import model as M, sharding as S
        import repro.models.blocks as BL

        cfg = dataclasses.replace(
            get_config("llama4-scout-17b-a16e").reduced(),
            n_experts=4, top_k=1)
        params = M.init_model_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab)
        ref, _, _ = M.forward(cfg, params, {"tokens": toks}, mode="train")
        mesh = make_mesh((2, 4), ("data", "model"))
        BL.MOE_A2A_CAPACITY_FACTOR = 4.0   # no drops -> exact
        with S.axis_rules(mesh, S.rules_for("train", moe_a2a=True)):
            got, _, _ = jax.jit(lambda p, t: M.forward(
                cfg, p, {"tokens": t}, mode="train"))(params, toks)
        err = float(jnp.max(jnp.abs(ref - got)))
        assert err < 1e-3, err
        print("ok", err)
    """)


def test_megatron_moe_matches_local_routing():
    run_sub("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import model as M, sharding as S

        cfg = get_config("grok-1-314b").reduced()   # 4 experts top-2
        params = M.init_model_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg.vocab)
        ref, _, _ = M.forward(cfg, params, {"tokens": toks}, mode="train")
        mesh = make_mesh((2, 4), ("data", "model"))
        with S.axis_rules(mesh, S.rules_for("train")):
            got, _, _ = jax.jit(lambda p, t: M.forward(
                cfg, p, {"tokens": t}, mode="train"))(params, toks)
        err = float(jnp.max(jnp.abs(ref - got)))
        assert err < 1e-3, err
        print("ok", err)
    """)


def test_sharded_train_step_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import model as M, sharding as S

        cfg = get_config("granite-3-2b").reduced()
        params = M.init_model_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab)
        lbl = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0,
                                 cfg.vocab)
        batch = {"tokens": toks, "labels": lbl}
        ref = float(M.loss_fn(cfg, params, batch))
        mesh = make_mesh((4, 2), ("data", "model"))
        with S.axis_rules(mesh, S.rules_for("train")):
            got = float(jax.jit(lambda p, b: M.loss_fn(cfg, p, b))(params,
                                                                   batch))
        assert abs(ref - got) < 1e-3, (ref, got)
        print("ok", ref, got)
    """)


@pytest.mark.slow
def test_dryrun_single_combo_subprocess():
    """The dry-run entry point itself (512 placeholder devices)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-3-2b", "--shape", "decode_32k", "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "1 ok, 0 skipped, 0 errors" in out.stdout


def test_engine_backend_builds_and_runs_each_instance_on_its_workers_device():
    """One worker per device: a cold start builds the warm instance on its
    worker's device, a prewarm on the device holding the fewest instances,
    and every batch runs where its instance lives."""
    out = run_sub("""
        import threading
        import jax, jax.numpy as jnp
        from repro.core.runtime import HOST_ACC, RuntimeDef, SimProfile
        from repro.gateway import EngineBackend, Gateway

        gate = threading.Barrier(4, timeout=60)

        def make(rid, wait):
            def setup():
                return jnp.zeros(4)
            def fn(data, config):
                if wait:
                    gate.wait()     # all four run at once: four workers
                h = config["handle"]
                ran = (h + 1).devices()
                return {"handle": h.devices().pop().id,
                        "ran": ran.pop().id}
            return RuntimeDef(runtime_id=rid, fn=fn, setup=setup,
                              profiles={HOST_ACC: SimProfile(0.01, 0.0)})

        backend = EngineBackend(max_batch=1)
        gw = Gateway(backend)
        for i in range(4):
            gw.register(make(f"cold{i}", True))
        for i in range(2):
            gw.register(make(f"pre{i}", False))
        assert len(jax.devices()) == 4
        futs = [gw.invoke(f"cold{i}", {"x": i}) for i in range(4)]
        res = [f.result() for f in futs]
        nodes = [f.invocation.node for f in futs]
        assert sorted(nodes) == [f"local/w{w}" for w in range(4)], nodes
        for f, r in zip(futs, res):
            w = int(f.invocation.node.rsplit("w", 1)[1])
            assert r == {"handle": w, "ran": w}, (f.invocation.node, r)
        # prewarms land on the least-loaded device, then run there
        backend.max_warm = 8
        assert backend.prewarm("pre0") and backend.prewarm("pre1")
        got = [gw.invoke(f"pre{i}", {}).result() for i in range(2)]
        assert [g["handle"] for g in got] == [0, 1], got
        assert all(g["ran"] == g["handle"] for g in got), got
        backend.shutdown()
        print("ok")
    """, devices=4, timeout=300)
    assert "ok" in out
