"""The device-trace reduction, pinned on a trace recorded on a v5e chip.

``data/serve_smoke_v5e.xplane.pb`` is granite-3-2b-smoke (bf16) served
through the gateway on one TPU v5 lite: two requests of 20 and 150
tokens, prefill chunk 64, four new tokens each, with the span tracer on
(``record_trace.py`` made it).  It holds whole-prefill, chunk and paged
decode programs and the engine's ``serve.step`` annotations.
"""
import os

import pytest

import _paths  # noqa: F401
import devtrace
import peaks

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_smoke_v5e.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return devtrace.reduce_file(DATA)


def test_busy_and_window(red):
    assert red["n_devices"] == 1
    assert red["busy_s"] == pytest.approx(0.000617691, rel=1e-9)
    # no window annotation in this trace: first to last device operation
    assert red["window_s"] == pytest.approx(0.033563393, rel=1e-9)


def test_paged_kernel_time_by_program(red):
    assert devtrace.kernel_seconds(red, "_decode_paged_impl",
                                   "pallas_paged") == pytest.approx(
        0.000192802, rel=1e-9)
    assert devtrace.kernel_seconds(red, "_chunk_batch_impl",
                                   "pallas_paged") == pytest.approx(
        0.000119165, rel=1e-9)
    # the whole-prefill program runs the flash kernel, not a paged one
    assert devtrace.kernel_seconds(red, "_prefill_install_impl",
                                   "pallas_paged") == 0.0
    assert devtrace.kernel_seconds(red, "_prefill_install_impl",
                                   "pallas") > 0.0


def test_self_time_sums_to_busy_time(red):
    # nested operations (a loop and its body) are counted once
    total = sum(red["op_seconds"].values())
    assert total == pytest.approx(red["busy_s"], rel=0.02)


def test_breakdown(red):
    ops = red["breakdown"]["device_ops"]
    assert len(ops) == devtrace.TOP
    assert ops[0][0] == "_decode_paged_impl:pallas_paged"
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "serve.step > PjitFunction(convert_element_type)"
    assert gaps[0][1] == pytest.approx(0.002982875, rel=1e-9)
    assert all(g[1] >= h[1] for g, h in zip(gaps, gaps[1:]))


def test_window_annotation_clips():
    import jax
    data = jax.profiler.ProfileData.from_file(DATA)
    planes = list(data.planes)
    full = devtrace.reduce_planes(planes)
    dev = [p for p in planes if p.name.startswith("/device:TPU:")][0]
    ops = [ln for ln in dev.lines if ln.name == "XLA Ops"][0]
    first = min(e.start_ns for e in ops.events)
    half = devtrace.reduce_planes(planes, window=(first, first + 0.5 *
                                                  full["window_s"] * 1e9))
    assert half["window_s"] == pytest.approx(full["window_s"] / 2)
    assert 0 < half["busy_s"] < full["busy_s"]


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
