#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 --control-seeds 13-15

For each seed: the cell's own step as the benchmark runs it, then the
numbers its check compares (the lower readings).  For each control seed:
the same numbers for the control, the next precision down from the
configuration's float32 (the upper readings):

* ``fp_bp`` cells: the program's own bfloat16 tile path
  (``compute_dtype="bfloat16"``);
* ``fbp`` cells: the reference itself in bfloat16 put in the program's
  place, and the program at JAX's default matmul precision (one bfloat16
  pass on the TPU) beside it.

One JSON line per reading on standard output.  The benchmark's runs never
call this; the limits in ``chipbench/checks/<cell>.json`` come from its
readings, as ``PERF.md`` records.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def _emit(**kw):
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["REPRO_AUTOTUNE"] = "0"
    os.environ["REPRO_TUNE_CACHE"] = "0"
    import jax
    import jax.numpy as jnp
    from chipbench import cells, reference
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    cell = cells.load(args.workload)
    kind = cell.traffic["step"]

    def reading(label, seed, **over):
        t0 = time.perf_counter()
        step = cell.step(seed, **over)
        step.setup()
        out = jax.block_until_ready(step.run())
        step.release()
        vals = step.check(out)
        _emit(cell=cell.name, run=label, seed=seed, seconds=round(
            time.perf_counter() - t0, 3), **vals)
        return step

    for seed in _seeds(args.seeds):
        reading("program", seed)
    for seed in _seeds(args.control_seeds):
        if kind == "fp_bp":
            reading("control_bf16_tiles", seed, compute_dtype="bfloat16")
        else:
            step = reading("program_default_precision", seed,
                           precision="default")
            zi = step.slices()
            part = jax.jit(lambda p: reference.fbp_slices(
                cell.cfg["geometry"], p, zi, dtype=jnp.bfloat16))(step.sino)
            g = cell.cfg["geometry"]["volume"]
            out = jnp.zeros((g["nx"], g["ny"], g["nz"]), jnp.float32).at[
                ..., jnp.asarray(zi)].set(part)       # only zi is compared
            _emit(cell=cell.name, run="control_reference_bf16", seed=seed,
                  **step.check(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
