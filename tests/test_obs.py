"""Observability subsystem: span trees, cross-process trace assembly,
exporters, and the Prometheus label-escaping regression.

The tracing contract under test (docs/observability.md):

* every settled invocation yields one root ``invocation`` span whose
  children *partition* [r_start, r_end] — summed child durations equal
  the measured RLat (exactly in the sim's virtual time, within 10% on
  live clocks);
* the tree has the same shape on all three backends, and on the cluster
  the ``execute``/engine spans are authored by the worker *process* and
  shipped home inside settle records — one contiguous trace assembled
  across process boundaries;
* a SIGKILLed worker's orphaned work is closed with an ``abandoned``
  ``attempt`` span, and the retry's spans link into the same trace;
* the disabled tracer is a no-op (no spans, no clock reads on the gated
  paths), so tracing costs nothing when off;
* the Chrome/Perfetto exporter emits structurally valid trace_event JSON
  (the bench-smoke CI step runs the same validator);
* ``prometheus_text`` escapes backslashes, quotes, and newlines in label
  values and carries ``# HELP``/``# TYPE`` for every family.
"""
import json
import time

import pytest

from repro import obs
from repro.core.accelerator import AcceleratorSpec
from repro.core.cluster import Cluster
from repro.core.events import Invocation
from repro.core.metrics import MetricsCollector, escape_label_value
from repro.core.runtime import RuntimeDef, SimProfile
from repro.gateway import EngineBackend, Gateway, SimBackend, Workflow
from repro.obs import ABANDONED, TRACER, validate_trace

GPU = AcceleratorSpec(type="gpu-k600", slots=2, mem_bytes=1 << 30,
                      cost_per_hour=0.5)

SLEEP_SPEC = "repro.cluster.runtimes:sleep_runtime"
ADD_SPEC = "repro.cluster.runtimes:add_runtime"


@pytest.fixture(autouse=True)
def _pristine_tracer():
    """Tracing state must never leak between tests (module singleton)."""
    obs.reset()
    yield
    obs.reset()


def sim_runtime(rid="r", elat=0.5, fn="echo"):
    """Profile-only (``fn=None``) keeps the sim fully virtual — ELat is
    drawn from the node's seeded RNG, so traces replay byte-identical."""
    if fn == "echo":
        fn = lambda data, config: {"echo": data}  # noqa: E731
    return RuntimeDef(
        runtime_id=rid,
        profiles={"gpu-k600": SimProfile(elat_median_s=elat,
                                         cold_start_s=1.0),
                  "host-jax": SimProfile(elat_median_s=0.01)},
        fn=fn)


def sim_gateway(fn="echo"):
    cl = Cluster(scheduler="warm", seed=0)
    cl.add_node("n0", [GPU])
    gw = Gateway(SimBackend(cl))
    gw.register(sim_runtime(fn=fn))
    return gw


def partition_errors(tr):
    """Per-root relative error between RLat and the summed durations of
    the root's *tiling* children — the acceptance-gate property.  An
    ``attempt`` span (a dead attempt's abandoned closure) deliberately
    overlaps the final attempt's queue_wait, so it is not part of the
    tiling."""
    spans = tr.spans()
    errs = {}
    for root in spans:
        if root.name != "invocation" or root.t_end is None:
            continue
        rlat = root.t_end - root.t_start
        ssum = sum(s.duration for s in spans
                   if s.parent_id == root.span_id and s.t_end is not None
                   and s.name != "attempt")
        errs[root.span_id] = 0.0 if rlat == 0 else abs(ssum - rlat) / rlat
    return errs


# ------------------------------------------------- disabled tracer: free
def test_disabled_tracer_is_a_noop():
    inv = Invocation(runtime_id="r", data_ref="d", r_start=0.0)
    assert TRACER.complete("execute", 0.0, 1.0) is None
    assert TRACER.begin("execute", trace="t") is None
    TRACER.record_invocation(inv)
    assert TRACER.spans() == []
    # gateways assign no trace context when tracing is off
    gw = sim_gateway()
    fut = gw.invoke("r", {"x": 1})
    fut.result()
    assert fut.invocation.trace_id is None
    assert fut.invocation.span_id is None
    assert TRACER.spans() == []


def test_record_abandoned_returns_relay_record_even_when_disabled():
    """Masters relay abandoned-span records to the client without running
    a tracer of their own — the record comes back regardless."""
    inv = Invocation(runtime_id="r", data_ref="d", r_start=1.0)
    inv.trace_id, inv.span_id, inv.n_start = "inv:7", "inv7", 2.0
    rec = TRACER.record_abandoned(inv, holder="w0", now=3.0, reason="dead")
    assert rec["status"] == ABANDONED and rec["name"] == "attempt"
    assert rec["t_start"] == 2.0 and rec["t_end"] == 3.0
    assert rec["parent_id"] == "inv7"
    assert TRACER.spans() == []         # nothing emitted locally
    # no trace context -> nothing to relay either
    bare = Invocation(runtime_id="r", data_ref="d", r_start=1.0)
    assert TRACER.record_abandoned(bare, holder="w0", now=3.0,
                                   reason="dead") is None


# ------------------------------------------- sim: deterministic + exact
def run_sim_traffic():
    gw = sim_gateway(fn=None)           # virtual ELat: seeded RNG only
    obs.enable(clock=gw.backend.now, metrics=gw.metrics)
    for i in range(4):
        gw.invoke("r", {"i": i}, at=0.25 * i)
    gw.drain()
    return gw, [s.to_record() for s in TRACER.spans()]


def normalized(records):
    """Invocation ids come from a process-global counter; rebase them so
    two identical runs compare equal (everything else must match).
    ``gc`` spans are left out: when the interpreter collects depends on
    its allocation history, not on the simulated traffic."""
    import re
    records = [r for r in records if r["name"] != "gc"]
    base = min((int(m.group(2)) for r in records
                for m in [re.search(r"inv(:?)(\d+)", r["span_id"])] if m),
               default=0)

    def fix(s):
        return None if s is None else re.sub(
            r"inv(:?)(\d+)",
            lambda m: f"inv{m.group(1)}{int(m.group(2)) - base}", s)

    out = []
    for r in records:
        r = dict(r)
        r["span_id"], r["parent_id"] = fix(r["span_id"]), fix(r["parent_id"])
        r["trace_id"] = fix(r["trace_id"])
        if r.get("attrs") and "inv_id" in r["attrs"]:
            r["attrs"] = {**r["attrs"],
                          "inv_id": r["attrs"]["inv_id"] - base}
        out.append(r)
    return out


def test_sim_partition_is_exact_and_deterministic():
    _, first = run_sim_traffic()
    errs = partition_errors(TRACER)
    assert len(errs) == 4
    assert all(e == 0.0 for e in errs.values()), errs
    # virtual clock -> byte-identical trace on replay
    obs.reset()
    _, second = run_sim_traffic()
    assert normalized(first) == normalized(second)


def test_sim_spans_feed_metrics_span_durations():
    gw, _ = run_sim_traffic()
    sd = gw.metrics.span_durations()
    ex = sd["r"]["execute"]
    assert ex["count"] == 4 and ex["total_s"] > 0
    assert ex["max_s"] <= ex["total_s"]
    text = gw.metrics.prometheus_text()
    assert '# TYPE hardless_span_seconds_total gauge' in text
    assert 'hardless_span_count{runtime="r",span="execute"} 4' in text


def test_workflow_steps_share_one_trace_with_workflow_root():
    gw = sim_gateway()
    obs.enable(clock=gw.backend.now)
    wf = Workflow("wf-sim")
    a = wf.step("s0", "r", payload={"x": 0})
    b = wf.step("s1", "r", after=a)
    wf.step("s2", "r", after=b)
    gw.submit_workflow(wf).result()
    roots = TRACER.find(name="invocation", trace="wf:wf-sim")
    assert len(roots) == 3
    assert all(r.parent_id == "wf:wf-sim" for r in roots)
    assert all(e == 0.0 for e in partition_errors(TRACER).values())


# --------------------------------------------------- engine: live clock
def test_engine_partition_within_ten_percent():
    gw = Gateway(EngineBackend())
    obs.enable(clock=gw.backend.now, metrics=gw.metrics)
    rdef = RuntimeDef(runtime_id="echo", profiles={},
                      fn=lambda data, config: {"echo": data})
    gw.register(rdef)
    futs = gw.map("echo", [{"i": i} for i in range(6)])
    for f in futs:
        f.result()
    gw.backend.shutdown()
    errs = partition_errors(TRACER)
    assert len(errs) == 6
    assert all(e <= 0.10 for e in errs.values()), errs
    # every settled invocation closed its root span (bench completeness)
    assert TRACER.closed_roots() == 6


# -------------------------------------------------- exporter / validator
def test_export_validate_roundtrip(tmp_path):
    run_sim_traffic()
    out = tmp_path / "trace.json"
    n = obs.export(str(out))
    doc = json.loads(out.read_text())
    assert len(doc["traceEvents"]) == n
    problems = validate_trace(doc)
    assert problems == [], problems
    # the X events carry microsecond ts/dur and the span identity
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all("span_id" in e["args"] for e in xs)
    assert all(e["dur"] >= 0 for e in xs)


def test_validator_rejects_structural_breakage():
    assert validate_trace({"no": "events"})
    assert validate_trace({"traceEvents": [{"ph": "X", "name": "x"}]})
    # unbalanced B without E
    bad = {"traceEvents": [
        {"ph": "B", "name": "x", "ts": 1.0, "pid": 1, "tid": 1}]}
    assert any("unclosed" in p for p in validate_trace(bad))
    # E with no B on the same track
    bad = {"traceEvents": [
        {"ph": "E", "name": "x", "ts": 1.0, "pid": 1, "tid": 1}]}
    assert any("without matching B" in p for p in validate_trace(bad))


# --------------------------------------------- prometheus escaping (fix)
def test_prometheus_escapes_hostile_label_values():
    assert escape_label_value('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    m = MetricsCollector()
    hostile = 'rt"quoted\\slash\nnewline'
    inv = Invocation(runtime_id=hostile, data_ref="d", r_start=0.0,
                     tenant='ten"ant\n')
    inv.n_start = inv.e_start = 0.0
    inv.e_end = inv.n_end = inv.r_end = 1.0
    inv.success = True
    m.record(inv)
    text = m.prometheus_text()
    assert '\\"quoted' in text and "\\\\slash" in text
    assert "\\nnewline" in text
    import re
    label_line = re.compile(
        r'[\w:]+\{(?:\w+="(?:[^"\\]|\\.)*",?)+\} \S+')
    for line in text.splitlines():      # every labeled sample still parses
        if line.startswith("#") or "{" not in line:
            continue
        assert label_line.fullmatch(line), line
    # every emitted family is preceded by HELP and TYPE
    families = {ln.split("{")[0].split(" ")[0]
                for ln in text.splitlines() if not ln.startswith("#")}
    helped = {ln.split(" ")[2] for ln in text.splitlines()
              if ln.startswith("# HELP")}
    typed = {ln.split(" ")[2] for ln in text.splitlines()
             if ln.startswith("# TYPE")}
    assert families <= helped and families <= typed


# -------------------------------------------- cluster: cross-process
def test_cluster_workflow_one_trace_contiguous_across_processes():
    """A 3-step workflow on the real multi-process cluster produces ONE
    trace whose span tree is contiguous: every span's parent resolves
    inside the trace, and the execute spans were authored by the worker
    process (they carry its pid), yet tile the client-side partition."""
    from repro.cluster import start_cluster
    h = start_cluster(2, heartbeat_timeout_s=10.0)
    try:
        gw = Gateway(h.backend)
        obs.enable(clock=h.backend.now, metrics=gw.metrics)
        rid = h.backend.register_spec(ADD_SPEC, {"add": 1})
        wf = Workflow("wf-cluster")
        a = wf.step("s0", rid, payload=0)
        b = wf.step("s1", rid, after=a)
        wf.step("s2", rid, after=b)
        out = gw.submit_workflow(wf).result()
        assert out == 3
        spans = TRACER.find(trace="wf:wf-cluster")
        by_id = {s.span_id: s for s in spans}
        roots = [s for s in spans if s.name == "invocation"]
        assert len(roots) == 3
        # contiguity: every parent link lands inside the same trace
        for s in spans:
            if s.parent_id is not None:
                assert s.parent_id in by_id, (s.span_id, s.parent_id)
        # the worker process authored execute (pid differs from ours)
        import os
        execs = [s for s in spans if s.name == "execute"]
        assert len(execs) == 3
        assert all(s.attrs["pid"] != os.getpid() for s in execs)
        assert all(s.attrs["node"] in ("w0", "w1") for s in execs)
        errs = partition_errors(TRACER)
        assert all(e <= 0.10 for e in errs.values()), errs
    finally:
        h.close()


def test_cluster_kill_worker_closes_abandoned_and_links_retry():
    """SIGKILL mid-batch: the keeper's requeue closes the dead attempt
    with an ``abandoned`` span, and the retry's spans join the SAME
    trace — the whole story of the invocation stays on one timeline."""
    from repro.cluster import start_cluster
    h = start_cluster(2, heartbeat_timeout_s=0.8, keeper_interval_s=0.1,
                      heartbeat_s=0.2)
    try:
        gw = Gateway(h.backend)
        obs.enable(clock=h.backend.now, metrics=gw.metrics)
        rid = h.backend.register_spec(SLEEP_SPEC, {"sleep_s": 0.3})
        futs = gw.map(rid, [{"i": i} for i in range(6)])
        time.sleep(0.1)                 # both workers now mid-sleep
        assert h.launcher.kill(0)
        for f in futs:
            f.result()
        abandoned = TRACER.find(name="attempt", status=ABANDONED)
        assert abandoned, "the kill must orphan at least one lease"
        retried = [i for i in gw.metrics.completed if i.attempt > 0]
        assert retried
        for sp in abandoned:
            # the abandoned closure hangs off the invocation's root ...
            roots = TRACER.find(name="invocation", trace=sp.trace_id)
            assert len(roots) == 1 and sp.parent_id == roots[0].span_id
            # ... and the *retry* attempt's children are in the same
            # trace, one attempt later
            a = sp.attrs["attempt"]
            nxt = [s for s in TRACER.find(trace=sp.trace_id)
                   if s.span_id.startswith(f"{sp.parent_id}/a{a + 1}/")]
            assert nxt, f"no attempt-{a + 1} spans joined {sp.trace_id}"
        # every settled invocation still closed a root span
        assert TRACER.closed_roots() == 6
        errs = partition_errors(TRACER)
        assert all(e <= 0.10 for e in errs.values()), errs
    finally:
        h.close()
