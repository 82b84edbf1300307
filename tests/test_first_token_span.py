"""The serving engine's ``first_token`` span (docs/observability.md).

One span per served request, from the request taking an engine slot to
its first token being on the host, under its own invocation's
``execute`` span, on the paged and the dense path; a request that is
preempted and resumed keeps one span; untraced, nothing is stamped.
"""
import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.gateway import EngineBackend, Gateway
from repro.models import model as M
from repro.obs import TRACER
from repro.serve.api import make_serve_runtime
from repro.serve.engine import Request, ServingEngine

CFG = get_config("granite-3-2b-smoke")


@pytest.fixture(autouse=True)
def _pristine_tracer():
    obs.reset()
    yield
    obs.reset()


def serve(payloads, max_new, traced=True, batch_wait_s=0.2, **engine):
    """Serve ``payloads`` through the gateway, one invocation each; the
    invocations and the warm engine."""
    gw = Gateway(EngineBackend(n_workers=1, max_batch=4,
                               batch_wait_s=batch_wait_s))
    rid = gw.register(make_serve_runtime(CFG, max_batch=4, **engine))
    run_cfg = {"max_new_tokens": max_new}
    assert gw.backend.prewarm(rid, run_cfg)
    if traced:
        obs.enable(clock=gw.backend.now)
    futs = gw.map(rid, payloads, config=run_cfg)
    for f in futs:
        f.result()
    eng = gw.backend.handle(gw.backend.warm_keys()[0])
    gw.backend.shutdown()
    return [f.invocation for f in futs], eng, gw.backend.batch_sizes


def _execute(inv):
    root = inv.span_id or f"inv{inv.inv_id}"
    return f"{root}/a{inv.attempt}/execute"


@pytest.mark.parametrize("page_size", [16, 0], ids=["paged", "dense"])
def test_one_first_token_span_per_request_under_its_invocation(page_size):
    prompts = [list(range(3, 3 + n)) for n in (5, 20, 11, 30)]
    invs, _, batches = serve([{"prompts": [p]} for p in prompts], 3,
                             max_slots=2, max_len=64, page_size=page_size,
                             prefill_chunk=8 if page_size else 0)
    assert max(batches) >= 2        # events shared a micro-batch
    spans = TRACER.find(name="first_token")
    assert len(spans) == len(invs)
    for inv, p in zip(invs, prompts):
        mine = [s for s in spans if s.trace_id == inv.trace_id]
        assert len(mine) == 1, (inv.inv_id, mine)
        s, = mine
        assert s.parent_id == _execute(inv)
        assert s.attrs == {"tokens": len(p)}
        assert inv.e_start <= s.t_start <= s.t_end <= inv.r_end


def test_preempted_request_keeps_one_span():
    # three 15-token prompts in two slots over three pool pages: decode
    # growth past a page preempts the youngest, which later re-prefills
    prompts = [[k + 1] * 15 for k in range(3)]
    invs, eng, _ = serve([{"prompts": prompts}], 6, max_slots=2, max_len=64,
                         page_size=16, kv_pool_tokens=48)
    assert eng.n_evictions > 0
    spans = TRACER.find(name="first_token")
    assert len(spans) == len(prompts)
    inv, = invs
    assert all(s.trace_id == inv.trace_id and s.parent_id == _execute(inv)
               and inv.e_start <= s.t_start <= s.t_end <= inv.r_end
               for s in spans)


def test_untraced_stamps_nothing():
    invs, _, _ = serve([{"prompts": [list(range(3, 23))]}], 3,
                       traced=False, max_slots=2, max_len=64, page_size=16,
                       prefill_chunk=8)
    assert invs[0].success
    assert TRACER.spans() == []
    eng = ServingEngine(CFG, M.init_model_params(CFG, jax.random.PRNGKey(0)),
                        max_slots=2, max_len=64, page_size=16)
    req = Request(prompt=list(range(3, 13)), max_new_tokens=2)
    eng.generate([req])
    assert req.t_admit is None and req.trace is None
    assert TRACER.spans() == []
