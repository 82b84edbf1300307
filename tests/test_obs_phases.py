"""Phases: what a host thread is doing, written once to the span tracer
and to the JAX profiler's timeline (docs/observability.md).

* off, ``Tracer.phase`` is one shared no-op context: no span, no
  annotation, and no ``gc`` hook left behind;
* on, each phase is a closed span and a profiler host event of the same
  name whose ``t`` argument is the span's start on the tracer's clock;
* the serving engine's step phases nest as the device trace's gap labels
  read them (``serve.readback`` inside ``decode`` inside ``serve.step``),
  on the paged and the dense path;
* the gateway dispatcher's wait, batch and persist are phases, and the
  synthetic ``batch_wait`` span is gone;
* each garbage collection while the tracer is on is one ``gc`` phase.
"""
import gc
import glob
import os
import time

import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.runtime import RuntimeDef
from repro.gateway import EngineBackend, Gateway
from repro.models import model as M
from repro.obs import TRACER
from repro.serve.engine import Request, ServingEngine

CFG = get_config("granite-3-2b-smoke")


@pytest.fixture(autouse=True)
def _pristine_tracer():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def params():
    return M.init_model_params(CFG, jax.random.PRNGKey(0))


def capture(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` capture; its host events
    as (name, start_ns, end_ns, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:CPU")
            for ln in p.lines for e in ln.events]


def inside(a, b):
    return b.t_start <= a.t_start and a.t_end <= b.t_end


def test_phase_off_is_one_shared_noop(tmp_path):
    assert TRACER.phase("serve.step") is TRACER.phase("gateway.wait",
                                                      queued=3)

    def body():
        with TRACER.phase("serve.offtest"):
            time.sleep(0.001)

    events = capture(tmp_path, body)
    assert not [e for e in events if e[0] == "serve.offtest"]
    assert TRACER.spans() == []
    assert TRACER._on_gc not in gc.callbacks


def test_phase_span_and_annotation_share_name_and_start(tmp_path):
    obs.enable()

    def body():
        for i in range(3):
            with TRACER.phase("serve.ontest", i=i):
                time.sleep(0.002)

    events = capture(tmp_path, body)
    spans = TRACER.find(name="serve.ontest")
    hosts = sorted((e for e in events if e[0] == "serve.ontest"),
                   key=lambda e: e[1])
    assert len(spans) == len(hosts) == 3
    for sp, (_, start_ns, end_ns, st) in zip(spans, hosts):
        assert sp.trace_id == "untraced" and sp.attrs == {"i": st["i"]}
        assert st["t"] == pytest.approx(sp.t_start, abs=1e-6)
    # the anchors put the profiler's clock on the tracer's: one offset
    offsets = [h[1] * 1e-9 - h[3]["t"] for h in hosts]
    assert max(offsets) - min(offsets) < 1e-3


def test_gc_hook_follows_the_tracer():
    obs.enable()
    assert TRACER._on_gc in gc.callbacks
    gc.collect()
    spans = TRACER.find(name="gc")
    assert spans and spans[-1].attrs["generation"] == 2
    assert spans[-1].attrs["collected"] >= 0
    obs.disable()
    assert TRACER._on_gc not in gc.callbacks
    n = len(TRACER.find(name="gc"))
    gc.collect()
    assert len(TRACER.find(name="gc")) == n
    obs.enable()
    obs.reset()
    assert TRACER._on_gc not in gc.callbacks


def _serve(params, page_size, force_gc=False):
    eng = ServingEngine(CFG, params, max_slots=2, max_len=64,
                        page_size=page_size,
                        prefill_chunk=8 if page_size else 0)
    if force_gc:
        real = eng._decode_paged
        forced = []

        def decode_and_collect(*a):
            if not forced:
                forced.append(gc.collect())
            return real(*a)
        eng._decode_paged = decode_and_collect
    reqs = [Request(prompt=list(range(3, 3 + n)), max_new_tokens=3,
                    req_id=i) for i, n in enumerate((5, 20))]
    with TRACER.ctx("t", "exec"):
        eng.generate(reqs)
    return eng


@pytest.mark.parametrize("page_size", [16, 0], ids=["paged", "dense"])
def test_engine_phases_nest(params, page_size):
    obs.enable()
    _serve(params, page_size)
    names = {s.name for s in TRACER.spans()}
    want = {"serve.step", "serve.admit", "serve.prep", "serve.readback",
            "serve.emit", "decode"}
    if page_size:
        want.add("serve.chunk")
    assert want <= names
    steps = TRACER.find(name="serve.step")
    decodes = TRACER.find(name="decode")
    for rb in TRACER.find(name="serve.readback"):
        d = [d for d in decodes if inside(rb, d)]
        assert len(d) == 1
        assert [s for s in steps if inside(d[0], s)]
    assert all(s.trace_id == "t" and s.parent_id == "exec"
               for s in TRACER.spans() if s.name.startswith("serve."))


def test_forced_collection_in_a_step_is_one_gc_span(params):
    obs.enable()
    auto = gc.isenabled()
    gc.disable()                # no collection of the interpreter's own
    try:
        _serve(params, 16, force_gc=True)
    finally:
        if auto:
            gc.enable()
    # JAX itself may run young-generation collections; the forced one is
    # the only full collection
    pauses = [s for s in TRACER.find(name="gc")
              if s.attrs["generation"] == 2]
    assert len(pauses) == 1
    assert [s for s in TRACER.find(name="serve.step")
            if inside(pauses[0], s)]


def test_engine_off_leaves_no_span_and_no_hook(params):
    _serve(params, 16)
    assert TRACER.spans() == []
    assert TRACER._on_gc not in gc.callbacks


def test_dispatcher_phases_replace_batch_wait():
    gw = Gateway(EngineBackend(max_batch=4))
    obs.enable(clock=gw.backend.now)
    gw.register(RuntimeDef(runtime_id="echo", profiles={},
                           fn=lambda data, config: {"echo": data}))
    for f in gw.map("echo", [{"i": i} for i in range(6)]):
        f.result()
    gw.backend.shutdown()
    names = [s.name for s in TRACER.spans()]
    assert "batch_wait" not in names
    batches = TRACER.find(name="gateway.batch")
    assert batches and sum(b.attrs["size"] for b in batches) == 6
    assert len(TRACER.find(name="gateway.persist")) == len(batches)
    waits = TRACER.find(name="gateway.wait")
    assert waits and all(w.attrs["queued"] >= 0 for w in waits)
    assert all(any(inside(p, b) for b in batches)
               for p in TRACER.find(name="gateway.persist"))
