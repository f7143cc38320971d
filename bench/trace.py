"""Reduction of a profiler trace to device time, idle gaps and their cause.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
lists: for each TPU, the op-level and module-level events; and the
benchmark's own host spans (``bench.*`` annotations, see ``probe.py``).
``reduce`` turns those lists into the numbers the metrics read:

- the traced window: the ``bench.traced_window`` span;
- per chip, busy seconds: the union of op intervals inside the window;
- per module (jitted program): executions and device seconds;
- the ops that took most device time;
- idle gaps, each attributed to what the host was doing at its middle on
  the engine thread that drives that chip.

Times are seconds on the trace's own clock. Host spans and device events
share it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced_window"

# what the engine thread was doing, innermost first: span prefix → label
GAP_CAUSES = (
    ("bench.admit", "admit: slot reset outside jit"),
    ("bench.step_dispatch", "step dispatch"),
    ("bench.tick", "tick after dispatch: argmax copy and slot loop"),
)
OUTSIDE_TICK = "between ticks: service loop, lock, idle wait"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
            devices[int(m.group(1))] = {
                "ops": lines.get(OPS_LINE, []),
                "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def _cause(t: float, engine_spans: Dict[str, list]) -> str:
    for prefix, label in GAP_CAUSES:
        for s, e in engine_spans.get(prefix, ()):
            if s <= t < e:
                return label
    return OUTSIDE_TICK


def reduce(ex: dict, engine_of_device: Dict[int, int]) -> Optional[dict]:
    """``engine_of_device``: device id → index of the engine on it. → the
    reduced trace, or None when the trace holds no device events."""
    devs = {d: v for d, v in ex["devices"].items() if d in engine_of_device}
    if not devs or not any(v["ops"] or v["modules"] for v in devs.values()):
        return None
    win = [(s, e) for n, s, e in ex["spans"] if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        evs = [(s, s + d) for v in devs.values() for _, s, d in
               (v["ops"] or v["modules"])]
        w0, w1 = min(s for s, _ in evs), max(e for _, e in evs)
    n = len(devs)
    busy, ops, modules = 0.0, defaultdict(float), {}
    gaps = defaultdict(float)
    for d, v in devs.items():
        events = v["ops"] or v["modules"]
        iv = _union(_clip([(s, s + dur) for _, s, dur in events], w0, w1))
        busy += sum(e - s for s, e in iv)
        for name, s, dur in v["ops"]:
            if w0 <= s < w1:
                ops[name] += dur / n
        for name, s, dur in v["modules"]:
            if w0 <= s < w1:
                c, t = modules.get(name, (0, 0.0))
                modules[name] = (c + 1, t + dur)
        idx = engine_of_device[d]
        mine = defaultdict(list)
        for name, s, e in ex["spans"]:
            base, _, eng = name.partition(":")
            if eng == str(idx):
                mine[base].append((s, e))
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_cause((a + b) / 2, mine)] += (b - a) / n
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return {"window_s": w1 - w0, "busy_s": busy / n, "chips": n,
            "modules": {k: {"count": c / n, "seconds": t / n}
                        for k, (c, t) in modules.items()},
            "device_ops": top(ops), "idle_gaps": top(gaps)}


def step_module(reduced: dict) -> Optional[Tuple[str, dict]]:
    """The jitted step: the module that took most device time."""
    if not reduced or not reduced["modules"]:
        return None
    return max(reduced["modules"].items(), key=lambda kv: kv[1]["seconds"])
