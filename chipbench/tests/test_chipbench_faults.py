"""A whole run on the CPU, past the look for a chip, with the timed path
sound and then broken underneath: ``correct`` must hold for the first
and fail for each fault a served cell can have, and for the control put
in the program's place."""
import time

import jax
import numpy as np
import pytest

import _paths  # noqa: F401
import harness
import run

MODEL = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 64, "d_ff": 512, "vocab": 512, "rope_theta": 10000.0,
         "tie_embeddings": True, "dtype": "bfloat16"}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
LIMIT = 0.01


def spec():
    return harness.Spec(
        name="cpu.chat",
        entry={"name": "cpu.chat", "config": "-", "traffic": "-",
               "chips": 1},
        cell={"rate_per_s": 3.0,
              "engine": {"slots": 2, "max_len": 128, "kv_pool_tokens": 256,
                         "page_size": 16, "prefill_chunk": 32},
              "limits": {"max_logit_gap": LIMIT}},
        config={"arch": "granite-3-2b-smoke", "reference": "dense_decoder",
                "model": MODEL},
        mix={"arrivals": {"process": "gamma", "cv": 2.0, "pattern_seed": 3},
             "prompt_tokens": {"dist": "lognormal", "median": 40,
                               "sigma": 0.6, "min": 8, "max": 100},
             "output_tokens": {"dist": "fixed", "value": 8}})


def measure(traced=False):
    return run.measure(spec(), 2 ** 32 + 9, 2.0, traced, jax.devices()[0],
                       PEAKS, time.monotonic())


def test_sound_run_is_correct():
    line, notes = measure()
    assert line["correct"], notes
    assert line["attempted"] == 6 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "rlat_p50_s", "rlat_mean_s",
                                    "tokens_per_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert notes[-2].startswith("check max_logit_gap")
    assert "compiles in window 0" in notes[1]


def test_traced_run_reads_span_metrics():
    line, notes = measure(traced=True)
    assert line["correct"], notes
    got = line["metrics"]
    for name in ("queue_wait_share", "decode_occupancy", "prefill_share",
                 "decode_step_ms", "compiles_in_window"):
        assert name in got, (name, got)
    assert 0 < got["decode_occupancy"]["value"] <= 100
    assert got["compiles_in_window"]["value"] == 0
    # no device plane on the CPU: device metrics stay silent, never 0
    assert "paged_decode_roofline" not in got and "step_mfu" not in got
    assert line["device"]["window_s"] > 0


def _alter_tokens(monkeypatch):
    from repro.serve import engine as E
    orig = E.ServingEngine._record_token

    def record(self, slot, req, tok):
        if len(req.output) == 3:
            tok = (tok + 1) % MODEL["vocab"]
        return orig(self, slot, req, tok)
    monkeypatch.setattr(E.ServingEngine, "_record_token", record)


def _keep_cache(monkeypatch):
    from repro.models import model as M
    orig = M.decode_step

    def step(cfg, params, cache, *a, **k):
        logits, _ = orig(cfg, params, cache, *a, **k)
        return logits, cache
    monkeypatch.setattr(M, "decode_step", step)


def _half_batch(monkeypatch):
    from repro.models import model as M
    orig = M.decode_step

    def step(cfg, params, cache, tokens, *a, **k):
        logits, new = orig(cfg, params, cache, tokens, *a, **k)
        half = tokens.shape[0] // 2
        return logits.at[half:].set(0.0), new
    monkeypatch.setattr(M, "decode_step", step)


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_cache, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line, notes = measure()
    assert not line["correct"], notes
    assert line["checks"]["max_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("seed", [2 ** 32 + 9, 5, 77])
def test_control_in_the_programs_place_is_not_correct(seed):
    """The whole run with the fp8 control judged in the program's place,
    as ``calibrate.py`` runs it on the chip.  At this size the program
    reads at most 0.0002 and the control 0.027-0.042 on these seeds, so
    the limit 0.01 lies between them."""
    line, notes = run.measure(spec(), seed, 2.0, False, jax.devices()[0],
                              PEAKS, time.monotonic(), control=True)
    assert line["failed"] == 0, notes
    assert not line["correct"], notes
    assert line["checks"]["max_logit_gap"]["value"] > LIMIT
    assert "fp8 control" in notes[-3]


def test_control_reads_above_the_program():
    """The fp8 control at a size a test run holds: on the same prompts
    and served tokens, the token it ranks first lies further below the
    float32 reference's best than any token the program served."""
    from references import dense_decoder as R
    from repro.serve.engine import Request, ServingEngine
    m = dict(MODEL, n_layers=8, d_model=512, n_heads=8, head_dim=64,
             d_ff=1024, vocab=16384)
    cfg = harness.program_config(harness.Spec(
        "", {}, {}, {"arch": "granite-3-2b-smoke", "model": m}, {}))
    w = R.make_weights(m, 2)
    eng = ServingEngine(cfg, w, max_slots=4, max_len=256, page_size=16,
                        prefill_chunk=64)
    rng = np.random.default_rng(2)
    prompts = [[int(x) for x in rng.integers(3, m["vocab"], n)]
               for n in (30, 90, 150, 60)]
    done = eng.generate([Request(prompt=p, max_new_tokens=24, req_id=i)
                         for i, p in enumerate(prompts)])
    outs = [r.output for r in sorted(done, key=lambda r: r.req_id)]
    prog = np.nanmax(R.served_gaps(m, w, prompts, outs))
    ctrl = np.nanmax(R.served_gaps(m, w, prompts, outs,
                                   control=True))
    assert ctrl > 3 * prog, (prog, ctrl)
