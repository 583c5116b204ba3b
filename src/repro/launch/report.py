"""Builds the EXPERIMENTS.md roofline tables from the dry-run JSON records +
the analytic model (see analysis.py for why both are needed).

    PYTHONPATH=src python -m repro.launch.report --dryrun results/dryrun
"""
from __future__ import annotations

import argparse
import json
import os

from repro import configs
from repro.launch import analysis, roofline
from repro.launch.shapes import SHAPES


def _fmt_s(x):
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def cell_row(arch: str, shape_name: str, rec: dict, tp=16, dp=16):
    # dp folds the pod axis in: multi-pod (2,16,16) -> dp=32, tp=16
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    a = analysis.analytic_cell(cfg, shape, tp=tp, dp=dp)
    n = rec.get("n_devices", 256)
    kind = roofline.DRYRUN_DEVICE_KIND
    t = roofline.terms(a["flops"], a["hbm_bytes"], a["collective_bytes"], n,
                       kind)
    mf = roofline.model_flops(cfg, shape, a["kind"])
    t["useful_fraction"] = mf / max(a["flops"], 1.0)
    t["mfu"] = mf / (n * roofline.peaks(kind)["flops"]
                     * max(t["step_s"], 1e-12))
    # validation: reconstruct what cost_analysis should see (loops counted once)
    meas = (rec.get("cost", {}).get("flops") or 0.0) * n
    pred = analysis.hlo_counted_flops(cfg, shape)
    t["hlo_validation"] = meas / pred if pred else float("nan")
    t["analytic"] = a
    t["hlo_measured_flops"] = meas
    # measured collectives with the layer-loop multiplier heuristic:
    coll = rec.get("collectives", {})
    per_comp = coll.get("per_computation_bytes", {})
    hlo_coll = 0.0
    for comp, b in per_comp.items():
        mult = cfg.n_layers if ("region" in comp or "while" in comp
                                or "body" in comp) else 1
        hlo_coll += b * mult
    t["hlo_collective_bytes"] = hlo_coll * n
    return t


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--out", default="results/roofline.md")
    args = ap.parse_args()

    rows = []
    for arch in configs.ARCHS:
        for shape_name in SHAPES:
            fn = os.path.join(args.dryrun,
                              f"{arch}-{shape_name}-{args.mesh}.json")
            if not os.path.exists(fn):
                continue
            rec = json.load(open(fn))
            if rec["status"] == "skipped":
                rows.append((arch, shape_name, None, rec["reason"]))
                continue
            if rec["status"] != "ok":
                rows.append((arch, shape_name, None, "ERROR"))
                continue
            t = cell_row(arch, shape_name, rec,
                         dp=(32 if args.mesh == "multi" else 16))
            t["temp_gib"] = (rec["memory"]["temp_bytes"] or 0) / 2 ** 30
            t["compile_s"] = rec.get("compile_s")
            rows.append((arch, shape_name, t, None))

    lines = ["| arch | shape | compute | memory | collective | bound | "
             "roofline-frac | MODEL/HLO | MFU@roof | temp/dev | HLOval |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for arch, shape, t, note in rows:
        if t is None:
            lines.append(f"| {arch} | {shape} | — | — | — | SKIP | — | — | — "
                         f"| — | {note[:60]} |")
            continue
        lines.append(
            f"| {arch} | {shape} | {_fmt_s(t['compute_s'])} | "
            f"{_fmt_s(t['memory_s'])} | {_fmt_s(t['collective_s'])} | "
            f"{t['bound']} | {t['roofline_fraction']:.2f} | "
            f"{t['useful_fraction']:.2f} | {t['mfu']:.2f} | "
            f"{t['temp_gib']:.1f}GiB | {t['hlo_validation']:.2f} |")
    out = "\n".join(lines)
    with open(args.out, "w") as f:
        f.write(out + "\n")
    print(out)


if __name__ == "__main__":
    main()
