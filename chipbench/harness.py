"""Drive one cell through the served path and measure it.

The system under test is entered where a user enters it: ``Gateway.invoke``
-> ``EngineBackend`` (its micro-batching dispatcher, ``max_batch`` = the
cell's slots) -> the warm runtime of ``make_serve_runtime`` ->
``ServingEngine`` (paged KV pool, chunked prefill) -> the Pallas kernels.
The harness makes the weights itself, from the seed, so that the
reference can make the same ones without taking anything from the
program; it hands them to the runtime's ``setup``.

Everything a cell, configuration or mix needs is found by name:
``cells/<workload>.json``, ``configs/<config>.json``, ``mixes/<traffic>.json``,
``references/<reference>.py`` and ``layer_metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# requests due in the window are awaited this long after it closes
AWAIT_CAP_S = 60.0
# served requests compared with the reference in every run
N_COMPARED = 6


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """A cell resolved from ``BENCHMARK.json`` and its files."""

    name: str
    entry: Dict
    cell: Dict
    config: Dict
    mix: Dict

    @property
    def model(self) -> Dict:
        return self.config["model"]

    @property
    def engine(self) -> Dict:
        return self.cell["engine"]


def load_spec(workload: str, root: str = ROOT) -> Spec:
    """The cell named ``workload``; ``KeyError`` if the benchmark has none."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    here = os.path.join(root, "chipbench")
    return Spec(workload, entry,
                load_json(here, "cells", f"{workload}.json"),
                load_json(here, "configs", f"{entry['config']}.json"),
                load_json(here, "mixes", f"{entry['traffic']}.json"))


def enable_cache() -> str:
    """JAX's persistent compilation cache in the program's placed
    directory (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
    says otherwise), holding every program however fast it compiled, so
    that only a cell's first run in a checkout compiles."""
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def reference_module(spec: Spec):
    return importlib.import_module(f"references.{spec.config['reference']}")


def program_config(spec: Spec):
    """The program's registered configuration with every size the
    configuration file states."""
    from repro.configs import get_config
    base = get_config(spec.config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {k: v for k, v in spec.model.items() if k in fields}
    return dataclasses.replace(base, **over)


def schedule(spec: Spec, seed: int, seconds: float,
             rate: Optional[float] = None) -> List[traffic.Due]:
    return traffic.schedule(spec.mix, rate or spec.cell["rate_per_s"],
                            seconds, seed, spec.model["vocab"])


# ----------------------------------------------------------------------
# the served instance
# ----------------------------------------------------------------------
class Served:
    """One warm runtime of the cell behind ``Gateway(EngineBackend())``."""

    def __init__(self, spec: Spec, weights, seed: int, max_new: int):
        from repro.gateway import EngineBackend, Gateway
        from repro.serve.api import make_serve_runtime
        from repro.serve.engine import ServingEngine
        cfg = program_config(spec)
        e = spec.engine
        kw = dict(max_slots=e["slots"], max_len=e["max_len"],
                  page_size=e["page_size"], prefill_chunk=e["prefill_chunk"],
                  kv_pool_tokens=e["kv_pool_tokens"])
        self.rdef = make_serve_runtime(cfg, max_batch=e["slots"], seed=seed,
                                       **kw)
        self.rdef.setup = lambda: ServingEngine(cfg, weights, greedy=True,
                                                sample_seed=seed, **kw)
        self.backend = EngineBackend(max_batch=e["slots"])
        self.gw = Gateway(self.backend)
        self.rid = self.gw.register(self.rdef)
        self.run_cfg = {"max_new_tokens": int(max_new)}
        self.vocab = spec.model["vocab"]
        if not self.backend.prewarm(self.rid, self.run_cfg):
            raise RuntimeError("the warm runtime could not be built")

    @property
    def engine(self):
        return self.backend.handle(self.backend.warm_keys()[0])

    def invoke(self, prompt: List[int], at: Optional[float] = None):
        return self.gw.invoke(self.rid, {"prompts": [prompt]},
                              config=self.run_cfg, at=at)

    def close(self) -> None:
        self.backend.shutdown()
        for key in self.backend.warm_keys():
            self.backend.evict_warm(key)


def _pages(n: int, page: int) -> int:
    return -(-n // page)


def table_width(context: int, page: int, max_len: int) -> int:
    """The engine's block-table width for a decode step whose longest
    sequence holds ``context`` tokens: its pages rounded up to a power of
    two, at most a full sequence's pages (``ServingEngine._decode_once``)."""
    n = _pages(context, page)
    return min(1 << max(n - 1, 0).bit_length(), max(_pages(max_len, page), 1))


def warmup_groups(lengths: List[int], counts: Dict[int, int], max_new: int,
                  slots: int, page: int, max_len: int) -> List[List[int]]:
    """Prompt lengths of the warm-up's groups; each group is served at
    once, each request generating two tokens.

    * one request for every distinct prompt length of the schedule: its
      whole-prefill or chunk programs, and a decode step;
    * one request for each decode table width the window can reach (from
      the shortest prompt to the longest prompt plus its output) that
      the first kind misses;
    * 2, 4, ... ``slots`` copies of the longest prompt, and of each length
      the schedule repeats: chunk steps that advance prompts together.
    """
    lo, hi = min(lengths), max(lengths)
    groups = [[n] for n in lengths]
    have = {table_width(n + 1, page, max_len) for n in lengths}
    for c in range(lo + 1, min(hi + max_new, max_len) + 1):
        w = table_width(c, page, max_len)
        if w not in have:
            have.add(w)
            groups.append([c - 1])
    k = 2
    while k <= slots:
        groups.append([hi] * k)
        groups += [[n] * k for n in lengths if n != hi and counts[n] > k // 2]
        k *= 2
    return groups


def warm_up(served: Served, sched: List[traffic.Due], seed: int) -> int:
    """Build every program the window will run on the warm instance
    itself, before the window; returns the requests it served.

    The gateway's events all carry the cell's ``max_new_tokens`` (it is
    part of the warm instance's key), so the warm-up drives the same
    instance's engine directly with two-token requests, then sends one
    event through the gateway."""
    import jax
    from repro.serve.engine import Request
    eng = served.engine
    counts: Dict[int, int] = {}
    for d in sched:
        counts[len(d.prompt)] = counts.get(len(d.prompt), 0) + 1
    rng = np.random.default_rng((int(seed) + 1) % (1 << 64))
    vocab = served.vocab
    groups = warmup_groups(sorted(counts), counts, sched[0].max_new_tokens,
                           eng.max_slots, eng.page, eng.max_len)
    # the dispatcher runs batches under ``jax.default_device``, which is
    # part of every jitted program's cache key: warm up under it too
    with jax.default_device(jax.devices()[0]):
        for g in groups:
            eng.generate([Request(prompt=[int(x) for x in
                                          rng.integers(3, vocab, n)],
                                  max_new_tokens=2, req_id=i)
                          for i, n in enumerate(g)])
    served.invoke(sched[0].prompt).result(extra_time_s=900.0)
    return sum(len(g) for g in groups) + 1


# ----------------------------------------------------------------------
# compiles, counted through jax.monitoring
# ----------------------------------------------------------------------
class CompileCounter:
    """Counts backend compiles and persistent-cache loads while on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **_: Any) -> None:
        if self.on and event in self.EVENTS:
            self.n += 1


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ServedRequest:
    due: float
    prompt: List[int]
    fut: Any
    late_s: float


def offer(served: Served, sched: List[traffic.Due], t0_mono: float,
          t0_backend: float) -> List[ServedRequest]:
    """Send each request at its due time (open loop): the event's RStart
    is its due time, so a late send counts in its latency."""
    out = []
    for d in sched:
        wait = t0_mono + d.due_s - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        due = t0_backend + d.due_s
        late = served.backend.now() - due
        out.append(ServedRequest(due, d.prompt, served.invoke(d.prompt,
                                                               at=due),
                                  late))
    return out


def settle(reqs: List["ServedRequest"], deadline_mono: float) -> None:
    """Wait for every request until ``deadline_mono`` at the latest."""
    for r in reqs:
        left = deadline_mono - time.monotonic()
        if left <= 0:
            return
        try:
            r.fut.result(extra_time_s=left)
        except Exception:  # noqa: BLE001 — a failed request is counted
            pass


def window(served: Served, sched: List[traffic.Due], seconds: float,
           lead: float = 0.05):
    """Offer ``sched`` open loop from ``lead`` seconds on and await every
    request up to ``AWAIT_CAP_S`` past the window's close: (outcomes, the
    window's start on the monotonic clock, and on the backend's)."""
    t0_mono = time.monotonic() + lead
    t0_backend = served.backend.now() + lead
    reqs = offer(served, sched, t0_mono, t0_backend)
    settle(reqs, t0_mono + seconds + AWAIT_CAP_S)
    return outcomes(reqs), t0_mono, t0_backend


def outcomes(reqs: List[ServedRequest]) -> List[Dict]:
    """Per request: due, timestamps (backend clock), output, success."""
    out = []
    for r in reqs:
        inv = r.fut.invocation
        ok = bool(inv.success and inv.r_end is not None)
        tokens: List[int] = []
        if ok:
            try:
                tokens = list(r.fut.result(extra_time_s=0.0)["outputs"][0])
            except Exception:  # noqa: BLE001
                ok = False
        out.append({"due": r.due, "n_start": inv.n_start,
                    "e_start": inv.e_start, "e_end": inv.e_end,
                    "r_end": inv.r_end, "ok": ok, "prompt": r.prompt,
                    "output": tokens, "late_s": r.late_s})
    return out


def pick_compared(done: List[Dict], seed: int, chunk: int) -> List[Dict]:
    """A sample of the finished requests, drawn from the seed: the one
    with the longest sequence, one that prefilled whole, one that
    prefilled in chunks, and others at random."""
    if not done:
        return []
    rng = np.random.default_rng((int(seed) + 2) % (1 << 64))
    order = [int(i) for i in rng.permutation(len(done))]
    picked = [max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["output"]))]
    for want_chunked in (False, True):
        for i in order:
            if (len(done[i]["prompt"]) > chunk) == want_chunked:
                if i not in picked:
                    picked.append(i)
                break
    for i in order:
        if len(picked) >= N_COMPARED:
            break
        if i not in picked:
            picked.append(i)
    return [done[i] for i in picked]
