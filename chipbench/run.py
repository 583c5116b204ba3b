#!/usr/bin/env python3
"""One run of one benchmark cell, on the TPU it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the system under test in
``src/repro``.  Set-up (JAX start, inputs made on the device from the
seed, compilation of the cell's programs, with JAX's persistent cache in
``<checkout>/.jax_cache``, and one warm-up step) is timed as ``setup_s``.
The window then runs for ``--seconds``: by default a closed loop, each
step after the last one finished, whose timed metric is the window's time
over the steps completed in it; a step kind with a ``window`` method of
its own drives its own loop and reports its own metrics.  ``--trace 1``
records the window with the profiler and reports the cell's per-layer
metrics instead.

After the window the peak device memory is read, the program is freed,
and the last step's outputs are compared with the plain reference
(``chipbench/reference.py``); each number compared is printed beside its
limit, last on standard error and last in the result line.  The last line
of standard output is the JSON result.  Without a TPU, or with fewer
chips than the cell asks for, the run exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench" / "trace"


def _say(msg: str):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def closed_loop(step, seconds: float, metric: str):
    """The default window: one caller, each step dispatched after the last
    one finished, until ``seconds`` have passed.  ``metric`` is the
    window's time over the steps completed in it.  Returns (last outputs,
    steps completed, {metric: value}).  A step kind that defines its own
    ``window(seconds)`` (an open loop with arrivals, say) returns the same
    three things and reports its own end-to-end metrics."""
    import jax
    from jax.profiler import TraceAnnotation
    times = []
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        with TraceAnnotation("chipbench.dispatch"):
            out = step.run()
        with TraceAnnotation("chipbench.wait"):
            jax.block_until_ready(out)
        times.append(time.perf_counter() - ts)
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    _say(f"{len(times)} steps: " + ",".join(f"{t:.6f}" for t in times))
    return out, len(times), {metric: window_s / len(times)}


def judge(values: dict, limits: dict) -> dict:
    """Each compared number beside its limit; NaN and a missing limit fail."""
    out = {}
    for name, v in values.items():
        lim = limits.get(name)
        ok = lim is not None and math.isfinite(v) and v <= lim
        out[name] = {"value": v, "limit": lim, "ok": ok}
    return out


def execute(cell, seed: int, seconds: float, trace: int, devs, t0: float):
    """Everything of a run after the look for the chip: set-up, window,
    reference check and, with ``trace``, the trace's reduction.  Returns
    the result object; ``t0`` is when the set-up clock started."""
    import jax
    from jax.profiler import TraceAnnotation
    from chipbench import cells, peaks, trace as tr
    step = cell.step(seed)
    step.setup()
    jax.block_until_ready(step.run())       # warm-up: nothing first-time in the window
    setup_s = time.perf_counter() - t0
    _say(f"{cell.name} seed {seed}: set-up {setup_s:.3f}s {step.notes}")

    hlo = tr.hlo_index(step.hlo_text()) if trace else None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only, no call tracing
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with TraceAnnotation("chipbench.window"):
            t_w = time.perf_counter()
            if hasattr(step, "window"):
                out, n, timed = step.window(seconds)
            else:
                out, n, timed = closed_loop(step, seconds,
                                            cell.timed_metrics[0])
            window_s = time.perf_counter() - t_w
    finally:
        if trace:
            jax.profiler.stop_trace()
    _say(f"window {window_s:.6f}s, {n} completed")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)

    step.release()
    checks = judge(step.check(out), cell.checks["limits"])
    del out
    correct = n > 0 and all(c["ok"] for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {"correct": correct, "attempted": n,
              "failed": 0 if correct else n}
    if trace:
        t = tr.read(tr.find_xplane(str(TRACE_DIR)), hlo,
                    cell.step_module.SCOPES)
        reading = tr.Reading(t, n, step.work(),
                             peaks.peaks(devs[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            v = cells.reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=t.busy_s(), window_s=t.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": t.top_ops(),
                                 "idle_gaps": t.idle_gaps()})
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in timed.items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result.update(metrics=metrics, device=device)
    result["program"] = step.notes
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        _say(f"check {k} {c['value']!r} limit {c['limit']!r} "
             + ("ok" if c["ok"] else "FAILED"))
    return result


def main(argv=None) -> int:
    t0 = time.perf_counter()
    args = _parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _say(f"no system under test: {ROOT / 'src' / 'repro'} is missing")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # Kernel configurations from the committed heuristics only: no sweep,
    # no measurements persisted by some earlier process.
    os.environ["REPRO_AUTOTUNE"] = "0"
    os.environ["REPRO_TUNE_CACHE"] = "0"
    from chipbench import cells
    cell = cells.load(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _say(f"needs a TPU, JAX found {devs[0].platform!r}")
        return 3
    if len(devs) < cell.chips:
        _say(f"{cell.name} needs {cell.chips} chips, JAX found {len(devs)}")
        return 3
    result = execute(cell, args.seed, args.seconds, args.trace,
                     devs[:cell.chips], t0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
