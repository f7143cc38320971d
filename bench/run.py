#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json``, one run, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system as users deploy it (gateway → EngineService →
ServingEngine → jitted decode step, one engine per chip), with weights
made on the chips from ``--seed``; warms up; offers the cell's traffic
through ``GatewayClient``s for a pre-roll and then for ``--seconds``; waits
for the answers; reads the peak memory; frees the program's state; checks
a seeded sample of the served tokens against the plain reference. The last
line of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a profiler trace of part of the window),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit. The checks are also the last lines of
stderr.

Without a TPU, with fewer chips than the cell asks for, or on a chip that
``bench/peaks.json`` does not list, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the persistent compile cache: a fixed directory inside the checkout, so
# only a cell's first run there compiles (set before JAX is imported)
CACHE_DIR = ROOT / ".jax_cache"
# seconds of the window that a --trace 1 run traces
TRACE_SECONDS = 4.0


def _stderr(msg: str):
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Refused(Exception):
    """No result can be measured here (no chip, too few, unknown kind)."""


def chips_for(jax, chips: int, peaks: dict):
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise Refused(f"no TPU: JAX's backend is {d.platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds "
                      f"{len(devices)}")
    if d.device_kind not in peaks:
        raise Refused(f"device kind {d.device_kind!r} is not in "
                      f"bench/peaks.json")
    return devices[:chips], peaks[d.device_kind]


class CompileStats:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events, with the time of each compile."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.compiles = []          # (perf_counter at the end, seconds)
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles.append((time.perf_counter(), duration))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def inside(self, w0, w1) -> int:
        return sum(1 for t, _ in self.compiles if w0 <= t < w1)


class GcPauses:
    """Garbage-collector passes, each as (start, seconds, generation), from
    ``gc.callbacks``: a pass stops every Python thread."""

    def __init__(self):
        self.passes, self._t0 = [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.passes.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def close(self):
        gc.callbacks.remove(self._cb)

    def inside(self, w0, w1) -> list:
        return [p for p in self.passes if w0 <= p[0] < w1]


def _memory_peak(devices) -> int:
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)


def _traced(window, seconds, trace_dir, out: dict):
    """Trace the first ``seconds`` of the window into ``trace_dir``."""
    import jax
    from bench.trace import WINDOW_SPAN
    delay = window[0] - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        time.sleep(max(0.0, min(seconds, window[1] - t0)))
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    out["host_window"] = (t0, t1)


def run(cell, seed: int, seconds: float, trace: bool, devices, peaks,
        t_start: float = T_START, control: bool = False) -> dict:
    """One run of ``cell`` on ``devices``. → the result object. With
    ``control`` (``bench/control.py``, never the benchmark's own runs) the
    result also holds ``control_gap``: the same check with the reference
    in bfloat16 put in the program's place."""
    import jax
    import numpy as np
    from bench import correct, manifest, readings
    from bench import trace as trace_mod
    from bench.generator import Traffic
    from bench.probe import Probe
    from bench.system import Load, build, make_params, warm_up

    precision = cell.config["serving"]["matmul_precision"]
    if precision != "default":
        jax.config.update("jax_default_matmul_precision", precision)
    stats = CompileStats(jax)
    ref, m = cell.reference(), cell.model
    params = make_params(ref, m, seed, devices)
    probe = Probe(annotate=trace)
    system = build(cell, params, devices, probe)
    try:
        warm_up(system)
        traffic = Traffic(cell.traffic, seed, m.vocab_size, seconds)
        load = Load(system, traffic, probe)
        gc_pauses = GcPauses()
        window = load.start()
        setup_s = window[0] - t_start
        threads = threading.active_count()
        traced, tracer = {}, None
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            tracer = threading.Thread(target=_traced, args=(
                window, TRACE_SECONDS, trace_dir, traced))
            tracer.start()
        drained = load.join()
        gc_pauses.close()
        if tracer is not None:
            tracer.join()
        crashes = sum(s.crashes for s in system.services)
        macs = load.macs_verified()
        memory_peak = _memory_peak(devices)
    finally:
        system.close()
    for e in system.engines:          # free the program's state
        e.state = e.params = None
    del system
    gc.collect()

    rec = readings.Run(cell, m, seconds, window, setup_s, load.results,
                       traffic.loop, probe, peaks)
    late = readings.lateness(rec)
    _stderr(f"window: {window[1] - window[0]:.3f} s, setup {setup_s:.3f} s, "
            f"compiles inside the window {stats.inside(*window)}, "
            f"persistent-cache hits {stats.hits}, all drained {drained}")
    if late and traffic.loop == "open":
        _stderr(f"generator lateness (s): median {np.median(late):.6f}, "
                f"p99 {np.percentile(late, 99):.6f}, max {max(late):.6f} "
                f"at +{readings.latest_send_at(rec):.3f} s over {len(late)} "
                f"sends")
    gaps = readings.longest_tick_gap(rec)
    passes = gc_pauses.inside(*window)
    _stderr("stalls: longest pause between an engine's ticks in the window "
            + (f"{gaps[0]:.6f} s at +{gaps[1]:.3f} s" if gaps else "none")
            + f"; gc passes in the window {len(passes)}, longest "
            + (f"{max(p[1] for p in passes):.6f} s" if passes else "none")
            + f", generation-2 passes {sum(p[2] == 2 for p in passes)}; "
            f"threads at the window's start {threads}")

    done = [(r, probe.admitted[r.key].engine) for r in load.results
            if r.tokens is not None and r.key in probe.admitted]
    corr = cell.config["correct"]
    sample = correct.pick_sample(done, corr["sample_requests"], seed,
                                 len(devices))
    numbers = correct.count_checks(load.results, probe, m.vocab_size,
                                   crashes, macs)
    numbers["max_gap"] = None
    if sample:
        t_ref = time.perf_counter()
        g = correct.gaps(ref, m, params[0], sample,
                         cell.config["serving"]["positions"], devices[0],
                         control=control)
        numbers["max_gap"] = g["max_gap"]
        _stderr(f"reference: {len(sample)} requests, "
                f"{g['served_tokens_checked']} served tokens compared in "
                f"{time.perf_counter() - t_ref:.1f} s")
    limits = {"max_gap": corr["max_gap"], "unverified_responses": 0,
              "tokens_not_the_engines": 0, "wrong_length_or_range": 0,
              "engine_crashes": 0}
    ok, checks = correct.verdict(numbers, limits)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(ok), "attempted": len(load.results),
              "failed": sum(1 for r in load.results if r.tokens is None)}
    if control and sample:
        result["control_gap"] = g["control_gap"]
    if trace:
        path = trace_mod.find_xplane(trace_dir)
        reduced = None
        if path is not None:
            reduced = trace_mod.reduce(
                trace_mod.extract(path),
                {d.id: i for i, d in enumerate(devices)})
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.trace = reduced
        rec.trace_host_window = traced.get("host_window")
        result["metrics"] = manifest.read_metrics(cell.per_layer, rec)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            _stderr("device modules: " + json.dumps(reduced["modules"]))
    else:
        result["metrics"] = manifest.read_metrics(cell.end_to_end, rec)
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        _stderr(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None):
    args = parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  the system under test
    except ImportError:
        _stderr("bench: the program (src/repro) is not in this checkout")
        return 2
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import manifest
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    try:
        cell = manifest.find_cell(args.workload)
        devices, peak = chips_for(jax, cell.chips, peaks)
    except (Refused, KeyError) as e:
        _stderr(f"bench: {e}")
        return 1
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
