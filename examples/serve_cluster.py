"""End-to-end serving driver (the paper's execution model with REAL JAX).

Two "pods" (nodes) advertise different accelerator types; two architectures
are registered as serverless runtimes. Events carry batches of generation
requests; node managers cold-start engines (jit compile + weights) on first
use, reuse them while warm, and persist results to object storage — the
full Hardless §IV lifecycle with actual model execution on this host.

Backend exercised: sim (pod cluster on the virtual clock) with REAL
CPU-sized (``<arch>-smoke``) JAX forwards inside each simulated node.

    PYTHONPATH=src python examples/serve_cluster.py
"""

from repro.configs import get_config
from repro.core.accelerator import AcceleratorSpec
from repro.core.cluster import Cluster
from repro.core.events import Invocation
from repro.core.runtime import SimProfile
from repro.data.tokenizer import ByteTokenizer
from repro.serve.api import make_serve_runtime

V5E_SLICE = AcceleratorSpec(type="v5e-4x4", slots=1, mem_bytes=16 << 30,
                            cost_per_hour=19.2, chips=16)
V5E_SMALL = AcceleratorSpec(type="v5e-2x2", slots=1, mem_bytes=16 << 30,
                            cost_per_hour=4.8, chips=4)

cluster = Cluster(scheduler="warm", seed=0)
cluster.add_node("pod0", [V5E_SLICE, V5E_SMALL])
cluster.add_node("pod1", [V5E_SMALL])

profiles = {
    "v5e-4x4": SimProfile(elat_median_s=0.2, cold_start_s=2.0),
    "v5e-2x2": SimProfile(elat_median_s=0.6, cold_start_s=2.0),
}
runtimes = {}
for arch in ("granite-3-2b-smoke", "qwen2.5-14b-smoke"):
    rdef = make_serve_runtime(get_config(arch),
                              acc_types=profiles, max_slots=4, max_len=64)
    cluster.register_runtime(rdef)
    runtimes[arch] = rdef

tok = ByteTokenizer()
prompts = [tok.encode(p) for p in
           ["the quick brown fox", "serverless accelerators", "hello"]]
data_ref = cluster.store.put({"prompts": prompts})

# async events: (runtime reference, data reference, run config) — the user
# never selects hardware; the platform routes to whatever slice is free.
for i in range(4):
    arch = ["granite-3-2b", "qwen2.5-14b"][i % 2]
    cluster.submit(Invocation(
        runtime_id=f"serve-{arch}-smoke", data_ref=data_ref,
        config={"max_new_tokens": 6}, r_start=float(i) * 0.5))

cluster.run(until=100_000.0)

print(f"events completed: {len(cluster.metrics.completed)}")
for inv in cluster.metrics.completed:
    res = cluster.store.get_outcome(inv.result_ref)["value"]
    print(f"  event {inv.inv_id}: rt={inv.runtime_id} acc={inv.accelerator} "
          f"cold={inv.cold_start} ELat={inv.elat:.2f}s "
          f"outputs={[len(o) for o in res['outputs']]} tokens")
for node in cluster.nodes:
    print(f"{node.name}: cold={node.n_cold_starts} warm={node.n_warm_starts}")
assert all(i.success for i in cluster.metrics.completed)
print("OK — serverless serving with real JAX execution")
