"""Cross-accelerator workflow: tiny-YOLO vision fan-out + Whisper audio
fan-in to one LLM captioner — three independent runtimes composed into a
single ``Workflow`` submission (the paper's multi-accelerator application,
e.g. VPU image recognition feeding a GPU language stage).

Every intermediate result flows step-to-step through the object store; the
client only submits the DAG and reads the final caption.

Backends exercised: ``--backend sim`` (default) places the steps on a
virtual-time VPU+GPU testbed while running REAL reduced JAX forwards;
``--backend engine`` executes the same workflow concurrently on this
host's JAX devices.  CI's examples-smoke job runs the sim path (CPU-only).

    PYTHONPATH=src python examples/workflow_pipeline.py [--backend engine]
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core.cluster import GPU_K600, VPU_NCS, Cluster
from repro.core.runtime import RuntimeDef, SimProfile
from repro.data.tokenizer import ByteTokenizer
from repro.gateway import (EngineBackend, Gateway, SimBackend, Workflow,
                           WorkflowStepError)
from repro.models import model as M
from repro.models.yolo import init_yolo_params, yolo_forward
from repro.serve.engine import Request, ServingEngine

HOST = "host-jax"


def vision_runtime() -> RuntimeDef:
    """tiny-YOLO image recognition — the paper's VPU workload."""
    def setup():
        return init_yolo_params(jax.random.PRNGKey(0))

    def fn(data, config):
        params = config.get("handle") or setup()
        logits = yolo_forward(params, data["image"])      # (1, h, w, 125)
        cells = logits.reshape(-1, logits.shape[-1])
        return {"detections": [int(i) for i in
                               np.asarray(cells.argmax(-1))[:4]]}

    return RuntimeDef(
        runtime_id="vision-tinyyolo",
        profiles={VPU_NCS.type: SimProfile(elat_median_s=1.577, sigma=0.04,
                                           cold_start_s=5.0),
                  HOST: SimProfile(elat_median_s=0.05)},
        fn=fn, setup=setup, artifact_bytes=60 << 20)


def audio_runtime() -> RuntimeDef:
    """Whisper-tiny transcription (CPU-sized config, stub mel frontend)."""
    cfg = get_config("whisper-tiny-smoke")

    def setup():
        return M.init_model_params(cfg, jax.random.PRNGKey(1))

    def fn(data, config):
        params = config.get("handle") or setup()
        rng = np.random.default_rng(data["audio_seed"])
        frames = rng.standard_normal(
            (1, cfg.n_frames, cfg.d_model)).astype("float32")
        toks = np.zeros((1, 8), "int32")
        logits, _, _ = M.forward(cfg, params,
                                 {"tokens": toks, "frames": frames})
        return {"transcript": [int(t) for t in
                               np.asarray(logits[0].argmax(-1))]}

    return RuntimeDef(
        runtime_id="audio-whisper-tiny",
        profiles={GPU_K600.type: SimProfile(elat_median_s=0.9,
                                            cold_start_s=3.0),
                  HOST: SimProfile(elat_median_s=0.2)},
        fn=fn, setup=setup, artifact_bytes=39 << 20)


def caption_runtime() -> RuntimeDef:
    """LLM captioner: fuses the gathered vision+audio outputs to a prompt
    and generates through a warm ServingEngine (jit + weights on cold)."""
    cfg = get_config("granite-3-2b-smoke")

    def setup():
        params = M.init_model_params(cfg, jax.random.PRNGKey(2))
        return ServingEngine(cfg, params, max_slots=2, max_len=48)

    def fn(data, config):
        engine = config.get("handle") or setup()
        # data = the gather barrier's list: vision outputs, then audio
        toks = [t for d in data
                for t in d.get("detections", []) + d.get("transcript", [])]
        prompt = [1] + [t % (cfg.vocab - 2) + 1 for t in toks][:12]
        done = engine.generate([Request(prompt=prompt, max_new_tokens=8)])
        return {"caption": done[0].output}

    return RuntimeDef(
        runtime_id="caption-lm",
        profiles={GPU_K600.type: SimProfile(elat_median_s=1.675,
                                            cold_start_s=3.0),
                  HOST: SimProfile(elat_median_s=0.4)},
        fn=fn, setup=setup, artifact_bytes=64 << 20)


def build_gateway(backend: str) -> Gateway:
    if backend == "sim":
        cluster = Cluster(scheduler="warm", seed=0)
        cluster.add_node("vpu-pod", [VPU_NCS])
        cluster.add_node("gpu-pod", [GPU_K600, GPU_K600])
        gw = Gateway(SimBackend(cluster))
    else:
        gw = Gateway(EngineBackend())
    for rdef in (vision_runtime(), audio_runtime(), caption_runtime()):
        gw.register(rdef)
    return gw


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="sim", choices=["sim", "engine"])
    ap.add_argument("--images", type=int, default=2,
                    help="vision fan-out width")
    args = ap.parse_args(argv)
    gw = build_gateway(args.backend)

    rng = np.random.default_rng(0)
    images = [{"image": rng.standard_normal((1, 64, 64, 3)).astype(
        "float32")} for _ in range(args.images)]

    wf = Workflow("caption-pipeline")
    sees = wf.fan_out("see", "vision-tinyyolo", payloads=images)
    hear = wf.step("hear", "audio-whisper-tiny", payload={"audio_seed": 7})
    wf.step("caption", "caption-lm", after=sees + [hear], retries=1)

    fut = gw.submit_workflow(wf)
    try:
        out = fut.result()
        ok = True
    except WorkflowStepError as e:      # the failing step, by name
        print(f"workflow failed: {e}")
        out, ok = None, False

    print(f"[{gw.backend.name}] workflow {fut.name!r}: {fut.statuses()}")
    for name in list(wf.steps):
        step_fut = fut.step_future(name)
        if step_fut is None:            # cancelled before submission
            print(f"  step {name:10s} (never submitted)")
            continue
        inv = step_fut.invocation
        print(f"  step {name:10s} acc={inv.accelerator:28s} "
              f"cold={int(inv.cold_start)} ELat={inv.elat:.3f}s")
    if ok:
        tok = ByteTokenizer()
        print(f"caption tokens: {out['caption']}")
        # untrained weights: ids above byte range are dropped before decode
        printable = [t for t in out["caption"] if t < tok.vocab_size]
        print(f"caption text  : {tok.decode(printable)!r} (untrained model)")
    print("pipeline", "COMPLETED" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
