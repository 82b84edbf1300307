#!/usr/bin/env python3
"""Record ``data/serve_smoke_v5e.xplane.pb`` on a chip (run there, once).

    python3 chipbench/tests/record_trace.py <output.xplane.pb>

granite-3-2b-smoke in bf16, served through ``Gateway(EngineBackend())``:
two requests of 20 and 150 tokens (prefill chunk 64, so one prefills
whole and one in chunks), four new tokens each, traced by the JAX
profiler with the span tracer on.
"""
import dataclasses
import glob
import os
import shutil
import sys
import tempfile

import _paths  # noqa: F401


def main(out: str) -> int:
    import jax
    import numpy as np
    from references import dense_decoder as R
    from repro import obs
    from repro.configs import get_config
    from repro.gateway import EngineBackend, Gateway
    from repro.serve.api import make_serve_runtime
    from repro.serve.engine import ServingEngine
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    cfg = dataclasses.replace(get_config("granite-3-2b-smoke"),
                              dtype="bfloat16")
    m = dict(n_layers=cfg.n_layers, d_model=cfg.d_model,
             n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
             head_dim=cfg.hd, d_ff=cfg.d_ff, vocab=cfg.vocab,
             rope_theta=cfg.rope_theta, tie_embeddings=True)
    w = R.make_weights(m, 5)
    kw = dict(max_slots=2, max_len=256, page_size=16, prefill_chunk=64)
    rdef = make_serve_runtime(cfg, max_batch=2, **kw)
    rdef.setup = lambda: ServingEngine(cfg, w, **kw)
    backend = EngineBackend(max_batch=2)
    gw = Gateway(backend)
    rid = gw.register(rdef)
    run = {"max_new_tokens": 4}
    backend.prewarm(rid, run)
    rng = np.random.default_rng(1)
    prompts = [[int(x) for x in rng.integers(3, 500, n)] for n in (20, 150)]
    for f in [gw.invoke(rid, {"prompts": [p]}, config=run) for p in prompts]:
        f.result(extra_time_s=300)
    obs.enable(clock=backend.now)
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for f in [gw.invoke(rid, {"prompts": [p]}, config=run) for p in prompts]:
        f.result(extra_time_s=300)
    jax.profiler.stop_trace()
    obs.disable()
    backend.shutdown()
    shutil.copy(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)[0], out)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
