"""The arithmetic the metric readers share, over one run's record.

A reader in ``bench/metrics/<name>.py`` calls one of these and returns
its number, or None where the run has nothing to read (a closed-loop run
has no latency from a schedule; a run without a device trace has no
device time).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from bench import counts
from bench import trace as trace_mod


@dataclass
class Run:
    """What one run recorded, for the readers."""
    cell: object                # manifest.Cell
    model: object               # manifest.Spec of the configuration's model
    seconds: float
    window: Tuple[float, float]
    setup_s: float
    results: list               # system.Result, every request sent
    loop: str                   # "open" | "closed"
    probe: object               # probe.Probe
    peaks: Optional[dict]       # the chip's row of peaks.json
    trace: Optional[dict] = None            # trace.reduce(...)
    trace_host_window: Optional[Tuple[float, float]] = None

    @property
    def slots(self) -> int:
        return self.cell.config["serving"]["slots"]

    @property
    def chips(self) -> int:
        return self.cell.chips


def _in(t, w):
    return w[0] <= t < w[1]


def window_results(run: Run) -> list:
    """Requests due (open loop) or sent (closed loop) inside the window."""
    return [r for r in run.results if _in(r.due, run.window)]


def latency_percentile(run: Run, q: float) -> Optional[float]:
    """Open loop: the q-th percentile of latency from the scheduled send to
    the verified response, over every request due in the window; one that
    failed counts as infinitely late."""
    if run.loop != "open":
        return None
    lat = [r.done - r.due if r.tokens is not None else math.inf
           for r in window_results(run)]
    if not lat:
        return None
    return float(np.percentile(lat, q))


def tokens_per_s(run: Run) -> Optional[float]:
    """Output tokens the engines produced inside the window, counted per
    token, for requests whose response the client received and
    verified."""
    answered = {r.key for r in run.results if r.tokens is not None}
    n = sum(v for k, v in run.probe.tokens_in_window.items() if k in answered)
    return n / run.seconds if n else None


def window_ticks(run: Run, window=None) -> list:
    w = window or run.window
    return [t for t in run.probe.ticks if _in(t.t1, w)]


def tick_ms(run: Run) -> Optional[float]:
    ticks = window_ticks(run)
    if not ticks:
        return None
    return 1e3 * float(np.mean([t.t1 - t.t0 for t in ticks]))


def slot_occupancy(run: Run) -> Optional[float]:
    ticks = window_ticks(run)
    if not ticks:
        return None
    return 100.0 * float(np.mean([len(t.positions) for t in ticks])) \
        / run.slots


def queue_wait_percentile(run: Run, q: float) -> Optional[float]:
    """From ``submit`` to the tick that gave the request a slot, over the
    requests admitted inside the window."""
    waits = [a.admitted_at - a.request.submitted_at
             for a in run.probe.admitted.values()
             if _in(a.admitted_at, run.window)]
    return float(np.percentile(waits, q)) if waits else None


def ipc_overhead_ms(run: Run) -> Optional[float]:
    """Median over the window's answered requests of the client's call
    time minus the service handler call that served it: the seal, MAC,
    ring, coalescing and gateway dispatch both ways."""
    vals = []
    for r in window_results(run):
        h = run.probe.handled.get(r.key) if r.tokens is not None else None
        if h is not None:
            vals.append((r.done - r.sent) - (h[1] - h[0]))
    return 1e3 * float(np.median(vals)) if vals else None


def cohort_wait_ms(run: Run) -> Optional[float]:
    """Median over the window's answered requests of the time from the
    engine retiring the request to the service handler returning it: the
    wait for the rest of a coalesced cohort (``handler_batch`` answers a
    cohort when all of it has retired)."""
    vals = []
    for r in window_results(run):
        if r.tokens is None:
            continue
        h, a = run.probe.handled.get(r.key), run.probe.admitted.get(r.key)
        if h is not None and a is not None and a.request.finished_at > 0:
            vals.append(h[1] - a.request.finished_at)
    return 1e3 * float(np.median(vals)) if vals else None


def step_device_s(run: Run) -> Optional[float]:
    """Device seconds of one execution of the jitted step, from the trace."""
    step = trace_mod.step_module(run.trace)
    if step is None or step[1]["count"] == 0:
        return None
    return step[1]["seconds"] / step[1]["count"]


def step_hbm_roofline(run: Run) -> Optional[float]:
    """Bytes the step needs over the chip's bandwidth, as a share of the
    step's device time, over the ticks of the traced window."""
    dev = step_device_s(run)
    ticks = window_ticks(run, run.trace_host_window) \
        if run.trace_host_window else []
    if dev is None or not ticks or run.peaks is None:
        return None
    nbytes = np.mean([counts.step_counts(run.model, t.positions)[1]
                      for t in ticks])
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / dev


def mfu(run: Run) -> Optional[float]:
    """Model FLOPs of every token the window's ticks processed, over the
    window times the chips' bf16 peak."""
    ticks = window_ticks(run)
    if not ticks or run.peaks is None:
        return None
    flops = sum(counts.step_counts(run.model, t.positions)[0] for t in ticks)
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"]
                            * run.chips)


def lateness(run: Run) -> List[float]:
    return [r.sent - r.due for r in window_results(run) if r.sent]


def latest_send_at(run: Run) -> float:
    """Seconds into the window of the send that ran furthest behind."""
    late = max((r for r in window_results(run) if r.sent),
               key=lambda r: r.sent - r.due)
    return late.due - run.window[0]


def longest_tick_gap(run: Run) -> Optional[Tuple[float, float]]:
    """The longest pause between one engine's consecutive ticks in the
    window, and when it began (seconds into the window)."""
    best = None
    for e in {t.engine for t in run.probe.ticks}:
        ticks = [t for t in window_ticks(run) if t.engine == e]
        for a, b in zip(ticks, ticks[1:]):
            if best is None or b.t0 - a.t1 > best[0]:
                best = (b.t0 - a.t1, a.t1 - run.window[0])
    return best
