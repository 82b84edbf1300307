"""Reduce a JAX profiler trace (``.xplane.pb``) of the measured window to
the numbers the per-layer metrics read.

* The window is the host annotation ``chipbench.window`` that the harness
  opens around the traced interval (the whole trace where it is absent).
* Busy time, per device: the union of the intervals of the operations on
  the device plane's ``XLA Ops`` line, clipped to the window; ``busy_s`` is
  its mean over the devices.
* Operation time: each operation's self time (its duration less the
  operations nested inside it, such as a loop's body), summed by program
  and kind.  The program is the ``XLA Modules`` event the operation
  starts in, named without ``jit_`` and the fingerprint.  The kind is the
  HLO name without its number; a Pallas kernel (``tpu_custom_call``) is
  ``pallas``, or ``pallas_paged`` where its first operand is an int32
  scalar-prefetch array (the block-table kernels).
* Idle gaps, on the first device: the longest stretches of the window
  with no operation running, each named by the outermost and innermost
  host events that cover its middle, among the engine's ``serve.step``
  annotation, the dispatch of a jitted program (``PjitFunction(...)``)
  and a read back to the host (``np.asarray``); ``host idle`` where none
  does.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
TOP = 10
_MODULE = re.compile(r"^jit_(.*)\(\d+\)$")
_PAGED = re.compile(r"custom-call\(s32\[")


def module_name(event_name: str) -> str:
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def op_kind(event_name: str) -> str:
    short = event_name.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in event_name:
        return "pallas_paged" if _PAGED.search(event_name) else "pallas"
    return re.sub(r"\.\d+$", "", short)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_planes(planes, window: Optional[Tuple[float, float]] = None
                  ) -> Dict:
    """The reduction of profiler planes (``ProfileData.planes``); times in
    the trace's nanoseconds."""
    planes = list(planes)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    hosts = [p for p in planes if p.name.startswith("/host:CPU")]
    host_events = []
    for p in hosts:
        for line in p.lines:
            for e in line.events:
                host_events.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name))
    if window is None:
        marks = [(s, e) for s, e, n in host_events if n == WINDOW]
        if marks:
            window = (marks[0][0], marks[-1][1])
    ops: Dict[Tuple[str, str], float] = {}
    busy_per_dev, gaps = [], []
    busy_starts: List[float] = []
    busy_ends: List[float] = []
    for di, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name))
                      for e in (lines["XLA Modules"].events
                                if "XLA Modules" in lines else []))
        starts = [m[0] for m in mods]
        spans: List[Tuple[float, float]] = []
        stack: List[list] = []      # [end, self_ns, key]

        def close(entry):
            ops[entry[2]] = ops.get(entry[2], 0.0) + entry[1]

        for e in (lines["XLA Ops"].events if "XLA Ops" in lines else []):
            s, d = e.start_ns, e.duration_ns
            if window and (s + d <= window[0] or s >= window[1]):
                continue
            spans.append((s, s + d))
            while stack and stack[-1][0] <= s:
                close(stack.pop())
            if stack:
                stack[-1][1] -= d
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            stack.append([s + d, d, (mod, op_kind(e.name))])
        while stack:
            close(stack.pop())
        busy = _union(spans)
        if busy:
            busy_starts.append(busy[0][0])
            busy_ends.append(busy[-1][1])
        lo, hi = window if window else (
            (busy[0][0], busy[-1][1]) if busy else (0.0, 0.0))
        busy = _clip(busy, lo, hi)
        busy_per_dev.append(sum(e - s for s, e in busy))
        if di == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    window_ns = (window[1] - window[0]) if window else (
        max(busy_ends) - min(busy_starts) if busy_starts else 0.0)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    marks = [h for h in host_events if _meaningful(h[2])]
    labelled = [(e - s, _label(marks, (s + e) / 2)) for s, e in longest]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": (sum(busy_per_dev) / len(busy_per_dev) * 1e-9
                   if busy_per_dev else 0.0),
        "window_s": window_ns * 1e-9,
        "n_devices": len(devices),
        "op_seconds": {f"{m}:{k}": v * 1e-9 for (m, k), v in ops.items()},
        "breakdown": {
            "device_ops": [[f"{m}:{k}", v * 1e-9] for (m, k), v in top_ops],
            "idle_gaps": [[name, d * 1e-9] for d, name in labelled],
        },
    }


def _meaningful(name: str) -> bool:
    """Host events that say what the host was doing: the engine's step
    annotation, dispatch of a jitted program, a read back to the host."""
    return (name == "serve.step" or name.startswith("PjitFunction(")
            or name.startswith("np.asarray"))


def _label(marks, t: float) -> str:
    """The outermost and innermost of ``marks`` that cover ``t``."""
    hits = sorted((s - e, name) for s, e, name in marks if s <= t <= e)
    if not hits:
        return "host idle"
    names = [hits[0][1]] + ([hits[-1][1]] if len(hits) > 1 else [])
    return " > ".join(names)


def reduce_file(path: str) -> Dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return reduce_planes(data.planes)


def reduce_dir(log_dir: str) -> Dict:
    """The reduction of the one trace that ``jax.profiler`` wrote under
    ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(files)}")
    return reduce_file(files[0])


def kernel_seconds(reduction: Dict, module: str, kind: str) -> float:
    """Summed self time of operations of ``kind`` in programs whose name
    contains ``module``."""
    return sum(v for k, v in reduction["op_seconds"].items()
               if k.split(":", 1)[1] == kind and module in k.split(":")[0])


def size_bytes(log_dir: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(log_dir) for f in fs)
