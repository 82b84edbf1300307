"""Serverless front door: model serving as Hardless runtimes.

``make_serve_runtime`` wraps a ServingEngine factory as a RuntimeDef whose
events are batches of generation requests — the node manager cold-starts
the engine (jit compile + weights) on first use and reuses it while warm,
exactly the paper's runtime-instance lifecycle, with real JAX execution.

The runtime is *batchable*: ``batch_fn`` merges several compatible events'
prompts into one shared continuous-batching stream, so a single jitted
decode step serves every event in the micro-batch (the gateway engine
dispatcher forms those batches; see ``gateway.backends.EngineBackend``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax

from repro.configs.base import ModelConfig
from repro.core.runtime import HOST_ACC, RuntimeDef, SimProfile
from repro.models import model as M
from repro.serve.engine import Request, ServingEngine


def make_serve_runtime(cfg: ModelConfig, *,
                       acc_types: Optional[Dict[str, SimProfile]] = None,
                       max_slots: int = 4, max_len: int = 128,
                       max_batch: int = 4,
                       page_size: int = 16, prefill_chunk: int = 0,
                       kv_pool_tokens: Optional[int] = None,
                       greedy: bool = True,
                       seed: int = 0) -> RuntimeDef:
    """RuntimeDef for serving ``cfg`` with REAL execution on this host.

    acc_types: accelerator type -> SimProfile (used for cold-start/result
    modeling; ELat itself is measured wall time of the actual forward).
    Defaults to the gateway engine backend's ``host-jax`` type.
    max_batch: largest event micro-batch one engine call may serve
    (their requests share the engine's decode slots).
    page_size: KV pool page size in tokens; 0 serves off the dense
    per-slot cache (the paged engine's differential reference).
    prefill_chunk: when > 0 (and the architecture supports it), prompts
    longer than this prefill in chunk-sized pieces interleaved with
    decode steps instead of stalling the whole batch.
    kv_pool_tokens: shared KV pool capacity (default max_slots*max_len).
    """
    if acc_types is None:
        acc_types = {HOST_ACC: SimProfile(elat_median_s=0.4, cold_start_s=2.0)}

    def setup():
        params = M.init_model_params(cfg, jax.random.PRNGKey(seed))
        return ServingEngine(cfg, params, max_slots=max_slots,
                             max_len=max_len, page_size=page_size,
                             prefill_chunk=prefill_chunk,
                             kv_pool_tokens=kv_pool_tokens, greedy=greedy,
                             sample_seed=seed)

    def _prompts(data: Any) -> List[List[int]]:
        # {"prompts": [...]} is the client form; {"outputs": [...]} is a
        # chained upstream serve step's stored result (its generations
        # become this step's prompts); a list is a workflow fan-in gather
        # (parent records in declared order, prompts concatenated) — this
        # is what makes serve runtimes composable in a Workflow without
        # any client-side adapter.
        if isinstance(data, list):
            return [p for d in data for p in _prompts(d)]
        return data["prompts"] if "prompts" in data else data["outputs"]

    def _requests(data: Any, max_new: int, base_id: int,
                  attempt: int = 0, trace=None) -> List[Request]:
        # the delivery attempt folds into each request's sampling key, so
        # an at-least-once redelivery draws fresh randomness instead of
        # replaying the lost attempt's stream; ``trace`` is the event's
        # trace context (traced runs), for each request's own spans
        prompts = [list(p) or [0] for p in _prompts(data)]
        return [Request(prompt=p, max_new_tokens=max_new,
                        req_id=base_id + i, attempt=attempt, trace=trace)
                for i, p in enumerate(prompts)]

    def fn(data: Any, config: Dict[str, Any]):
        engine: Optional[ServingEngine] = config.get("handle")
        if engine is None:                      # node skipped setup (sim)
            engine = setup()
        max_new = int(config.get("max_new_tokens", 8))
        done = engine.generate(_requests(
            data, max_new, base_id=0,
            attempt=int(config.get("attempt", 0))))
        return {"outputs": [r.output for r in done],
                "n_decode_steps": engine.n_decode_steps}

    def batch_fn(datas: List[Any], config: Dict[str, Any]):
        engine: Optional[ServingEngine] = config.get("handle")
        if engine is None:
            engine = setup()
        max_new = int(config.get("max_new_tokens", 8))
        attempts = list(config.get("attempts") or [])
        attempts += [0] * (len(datas) - len(attempts))
        traces = list(config.get("traces") or [])
        traces += [None] * (len(datas) - len(traces))
        groups, base = [], 0
        for data, attempt, trace in zip(datas, attempts, traces):
            reqs = _requests(data, max_new, base_id=base, attempt=attempt,
                             trace=trace)
            base += len(reqs)
            groups.append(reqs)
        done_groups = engine.generate_many(groups)
        return [{"outputs": [r.output for r in g],
                 "n_decode_steps": engine.n_decode_steps}
                for g in done_groups]

    return RuntimeDef(runtime_id=f"serve-{cfg.name}", profiles=acc_types,
                      fn=fn, setup=setup,
                      batch_fn=batch_fn, max_batch=max_batch,
                      artifact_bytes=64 << 20)
