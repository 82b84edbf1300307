"""The program's phases read by the benchmark: the device trace put on
the span tracer's clock (``tracer_clock.py``), the per-layer readers that
read phases (``idle_pending_share``, ``step_host_ms``, ``gc_pause_s``),
and the split of idle time by cause (``idle_causes.py``), on synthetic
planes and records."""
import os
from types import SimpleNamespace as NS

import pytest

import _paths  # noqa: F401
import devtrace
import idle_causes
import tracer_clock
from layer_metrics import gc_pause_s, idle_pending_share, step_host_ms

OFF = 1000.0            # profiler seconds minus tracer seconds


def ev(name, s_ns, d_ns, **stats):
    return NS(name=name, start_ns=s_ns, duration_ns=d_ns,
              stats=list(stats.items()))


def planes(host, ops):
    return [NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
            NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])]


def anchored(name, t_s, d_s, jitter_ns=0):
    """A phase's host event whose ``t`` is its start on the tracer clock."""
    return ev(name, (t_s + OFF) * 1e9 + jitter_ns, d_s * 1e9, t=t_s)


def test_clock_offset_is_the_median_over_anchors():
    host = [anchored("serve.step", 1.0, 0.1, 300),
            anchored("serve.readback", 1.05, 0.01, -200),
            anchored("gc", 2.0, 0.5, 100),
            ev("PjitFunction(f)", 5e9, 1e6)]
    assert tracer_clock.clock_offset_s(planes(host, [])) == \
        pytest.approx(OFF + 100e-9, abs=1e-12)
    # no phase carries ``t``: no offset (a program without phases)
    assert tracer_clock.clock_offset_s(
        planes([ev("serve.step", 1e9, 1e6)], [])) is None


def test_idle_intervals_cover_window_less_busy():
    w0, w1 = (OFF + 1.0) * 1e9, (OFF + 2.0) * 1e9
    host = [ev(devtrace.WINDOW, w0, w1 - w0)]
    ops = [ev("fusion", w0 - 1e8, 2e8),             # clipped at the start
           ev("copy", w0 + 3e8, 1e8), ev("copy.1", w0 + 3.5e8, 1e8),
           ev("fusion.2", w0 + 8e8, 1e8)]
    pl = planes(host, ops)
    idle = tracer_clock.idle_intervals(pl, OFF)
    assert [x for iv in idle for x in iv] == \
        pytest.approx([1.1, 1.3, 1.45, 1.8, 1.9, 2.0])
    red = devtrace.reduce_planes(pl)
    total = sum(e - s for s, e in idle)
    assert total == pytest.approx(red["window_s"] - red["busy_s"])
    # no offset: the trace's own clock, in seconds
    assert tracer_clock.idle_intervals(pl)[0] == \
        pytest.approx([OFF + 1.1, OFF + 1.3])


def span(name, t0, t1, **attrs):
    return {"name": name, "t_start": t0, "t_end": t1, "attrs": attrs,
            "trace_id": "t", "span_id": name, "parent_id": None}


def record(spans=(), busy=4.0, window=20.0, idle=None, offset=None):
    reqs = [{"due": 1.0, "r_end": 6.0, "ok": True},
            {"due": 3.0, "r_end": 8.0, "ok": True},
            {"due": 12.0, "r_end": 14.0, "ok": True}]
    trace = {"busy_s": busy, "window_s": window}
    if idle is not None:
        trace.update(clock_offset_s=offset, idle_intervals=idle)
    return {"requests": reqs, "spans": list(spans), "trace": trace}


def test_idle_pending_share_is_pending_time_less_busy_time():
    # outstanding 1-8 and 12-14 (9 s), busy 4 s inside it: 5 s of 20
    assert idle_pending_share.read(record()) == pytest.approx(25.0)
    rec = record()
    rec["requests"][1]["r_end"] = None          # never came back
    assert idle_pending_share.read(rec) is None


def steps():
    return [span("serve.step", 1.0, 1.060),
            span("serve.prep", 1.001, 1.004),
            span("decode", 1.003, 1.055, tokens=2),
            span("serve.readback", 1.010, 1.050),
            span("serve.emit", 1.055, 1.058),
            span("serve.step", 1.060, 1.110),
            span("decode", 1.062, 1.108, tokens=2),
            span("serve.readback", 1.070, 1.100),
            span("serve.step", 1.2, 1.21),            # admission only
            span("gc", 2.0, 2.5, generation=2, collected=10),
            span("gc", 13.5, 15.0, generation=0, collected=0)]


def test_step_host_ms_is_step_less_readback():
    # (60 - 40) and (50 - 30) ms; the step with no decode is not counted
    assert step_host_ms.read(record(steps())) == pytest.approx(20.0)
    assert step_host_ms.read(record([span("decode", 1.0, 1.05)])) is None


def test_gc_pause_s_sums_collections_while_serving():
    # 0.5 s, and 0.5 s of the second before the last result at 14.0
    assert gc_pause_s.read(record(steps())) == pytest.approx(1.0)
    # a program without phases has no counter: nothing, not zero
    assert gc_pause_s.read(record([span("decode", 1.0, 1.05)])) is None


def test_idle_split_adds_up_and_names_phases():
    idle = [[0.0, 1.005], [1.012, 1.049], [2.0, 2.6], [8.0, 12.5],
            [14.0, 20.0]]
    rec = record(steps() + [span("gateway.wait", 7.5, 12.2, queued=0)],
                 idle=idle, offset=OFF)
    out = idle_causes.split(rec)
    sec = out["seconds"]
    total = sum(e - s for s, e in idle)
    assert sum(sec.values()) == pytest.approx(total)
    assert sec["serve.readback"] == pytest.approx(0.037)
    assert sec["gc"] == pytest.approx(0.5)
    assert sec["serve.prep"] == pytest.approx(0.003)
    assert sec["serve.step"] == pytest.approx(0.002)
    # of 8-12.5 only 12-12.5 has a request outstanding: the dispatcher
    # waited to 12.2, then no phase held the host
    assert sec["gateway.wait"] == pytest.approx(0.2)
    assert sec["unnamed"] == pytest.approx(0.1 + 0.3)
    assert sec["nothing outstanding"] == pytest.approx(1.0 + 4.0 + 6.0)
    assert [[round(x, 9) for x in g[:2]] + g[2:] for g in out["long"]] == \
        [[4.5, 0.5, "unnamed"], [0.6, 0.6, "gc"]]
    assert idle_causes.split(record(steps())) is None


def test_traced_cpu_run_reads_the_phase_metrics(monkeypatch):
    import run
    from test_chipbench_faults import measure
    records = []
    real = run.layer_metrics
    monkeypatch.setattr(run, "layer_metrics",
                        lambda spec, rec: records.append(rec) or
                        real(spec, rec))
    line, notes = measure(traced=True)
    assert line["correct"], notes
    rec, = records
    assert step_host_ms.read(rec) > 0
    assert gc_pause_s.read(rec) >= 0
    # no device plane on the CPU: the device metric stays silent
    assert idle_pending_share.read(rec) is None


PHASES = os.path.join(os.path.dirname(__file__), "data",
                      "serve_phases_v5e.xplane.pb")


def host_phases(planes, offset):
    """The phases' host events of a saved trace as span records on the
    tracer's clock."""
    return [{"name": e.name, "t_start": e.start_ns * 1e-9 - offset,
             "t_end": (e.start_ns + e.duration_ns) * 1e-9 - offset}
            for p in planes if p.name.startswith("/host:CPU")
            for ln in p.lines for e in ln.events
            if tracer_clock.is_phase(e.name)]


@pytest.fixture(scope="module")
def phased():
    """``data/serve_phases_v5e.xplane.pb``: the two-request smoke of
    ``serve_smoke_v5e.xplane.pb`` (``record_trace.py``) recorded again on a
    TPU v5 lite once the program wrote phases."""
    import jax
    return list(jax.profiler.ProfileData.from_file(PHASES).planes)


def test_recorded_phases_put_the_trace_on_the_tracer_clock(phased):
    off = tracer_clock.clock_offset_s(phased)
    assert off == pytest.approx(-7.216661565, abs=1e-9)
    idle = tracer_clock.idle_intervals(phased, off)
    red = devtrace.reduce_planes(phased)
    assert len(idle) == 196
    assert sum(e - s for s, e in idle) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert red["window_s"] - red["busy_s"] == pytest.approx(0.033059854,
                                                            rel=1e-9)
    # every anchor agrees with the median to within 10 microseconds
    anchors = [e.start_ns * 1e-9 - dict(e.stats)["t"]
               for p in phased if p.name.startswith("/host:CPU")
               for ln in p.lines for e in ln.events
               if tracer_clock.is_phase(e.name)]
    assert len(anchors) == 30
    assert max(abs(a - off) for a in anchors) < 1e-5
    assert {p["name"] for p in host_phases(phased, off)} == {
        "gateway.batch", "gateway.persist", "serve.step", "serve.admit",
        "serve.chunk", "serve.prep", "serve.readback", "serve.emit"}


def test_recorded_gaps_are_named_by_phase(phased):
    off = tracer_clock.clock_offset_s(phased)
    idle = tracer_clock.idle_intervals(phased, off)
    tl = idle_causes.timeline(host_phases(phased, off))
    longest = sorted(idle, key=lambda iv: iv[0] - iv[1])[:2]
    names = [idle_causes.named([iv], tl) for iv in longest]
    # the whole-prefill admission's dispatch, then a token read-back
    assert [n[0][1] for n in names] == ["serve.admit", "serve.readback"]
    assert names[0][0][0] == pytest.approx(0.002914592, rel=1e-6)
