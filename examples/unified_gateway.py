"""One client, two execution worlds: the SAME ``invoke()`` code runs
against the calibrated cluster simulation and against real JAX execution
on this host — only the backend handed to the Gateway changes.

Backends exercised: BOTH — sim (roofline service times, virtual clock)
then engine (real execution of the CPU-sized ``-smoke`` config on this
host's JAX devices).

    PYTHONPATH=src python examples/unified_gateway.py
"""
from repro.configs import get_config
from repro.core.accelerator import AcceleratorSpec
from repro.core.cluster import Cluster
from repro.core.runtime import RuntimeDef
from repro.data.tokenizer import ByteTokenizer
from repro.gateway import EngineBackend, Gateway, SimBackend
from repro.serve.api import make_serve_runtime
from repro.serve.service_model import roofline_profile

ARCH = "granite-3-2b"
PROMPTS = ["the quick brown fox", "serverless accelerators", "hardless"]


def run_client(gw: Gateway, runtime_id: str) -> None:
    """The serverless client — identical for every backend: stage data,
    fan out events, poll futures, read results from object storage."""
    tok = ByteTokenizer()
    payloads = [{"prompts": [tok.encode(p)]} for p in PROMPTS]
    futs = gw.map(runtime_id, payloads, config={"max_new_tokens": 4},
                  at=0.0, spacing_s=0.5)
    gw.drain()
    name = gw.backend.name
    for fut in futs:
        inv = fut.invocation
        assert fut.poll(), f"result for ev{fut.inv_id} not in object store"
        fut.result()    # raises if the invocation failed
        print(f"  [{name}] ev{fut.inv_id} cold={int(inv.cold_start)} "
              f"ELat={fut.elat:.3f}s RLat={fut.rlat:.3f}s")
    s = gw.summary()
    print(f"  [{name}] ELat p50 = {s['elat_p50']:.3f}s, "
          f"cold starts = {s['cold_starts']}, "
          f"RSuccess = {s['r_success']}/{s['n_completed']}")


# -- backend 1: calibrated simulation (full-size config, no hardware) ----
print("sim backend (event-driven cluster, roofline service times):")
cfg_full = get_config(ARCH)
cluster = Cluster(scheduler="warm", seed=0)
cluster.add_node("pod0", [AcceleratorSpec(type="v5e-4x4", slots=1,
                                          mem_bytes=16 << 30,
                                          cost_per_hour=19.2, chips=16)])
sim_gw = Gateway(SimBackend(cluster))
sim_gw.register(RuntimeDef(
    runtime_id=f"serve-{cfg_full.name}",
    profiles={"v5e-4x4": roofline_profile(cfg_full, batch=1, new_tokens=4)}))
run_client(sim_gw, f"serve-{cfg_full.name}")

# -- backend 2: real JAX engine on this host (CPU-sized config) ----------
print("engine backend (real execution: cold = jit + weights, warm = reuse):")
cfg_red = get_config(f"{ARCH}-smoke")
eng_gw = Gateway(EngineBackend())
eng_gw.register(make_serve_runtime(cfg_red, max_slots=2, max_len=48))
run_client(eng_gw, f"serve-{cfg_red.name}")
