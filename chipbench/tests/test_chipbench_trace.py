"""The reduction from a profiler trace to per-layer readings."""
from __future__ import annotations

import pathlib

import pytest

from chipbench import cells, trace as tr

HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %iota = s32[8]{0} iota(), iota_dimension=0
  ROOT %gather.3 = f32[8]{0} gather(%param_0, %iota), offset_dims={}, metadata={op_name="jit(recon)/fbp/while/body/gather"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %custom-call.2 = f32[8]{0:T(128)} custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", backend_config={"x": 1}, metadata={op_name="jit(step)/fp/pallas_call"}
  %transpose.4 = f32[8]{0} transpose(%custom-call.2), dimensions={0}, metadata={op_name="jit(step)/fp/transpose"}
  %fusion.5 = f32[8]{0} fusion(%transpose.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/bp/take"}
  ROOT %add.6 = f32[8]{0} add(%fusion.5, %Arg_0.1), metadata={op_name="jit(step)/residual/sub"}
}
"""


def test_hlo_index_classifies_ops():
    idx = tr.hlo_index(HLO)
    assert idx["custom-call.2"].kind == "pallas"
    assert idx["transpose.4"].kind == "other"
    assert idx["fusion.5"].kind == "gather"
    assert idx["gather.3"].kind == "gather"
    assert idx["add.6"].opcode == "add"
    scopes = ("fp", "residual", "bp", "fbp")
    assert tr.scope_of(idx["custom-call.2"].op_name, scopes) == "fp"
    assert tr.scope_of(idx["fusion.5"].op_name, scopes) == "bp"
    assert tr.scope_of(idx["gather.3"].op_name, scopes) == "fbp"
    assert tr.scope_of("jit(x)/other/op", scopes) == ""


def _synthetic():
    ms = 1e6
    ops = [tr.Op("custom-call.2", "fp", "pallas", 1 * ms, 4 * ms, "d0"),
           tr.Op("transpose.4", "fp", "other", 3 * ms, 3 * ms, "d0"),
           tr.Op("fusion.5", "bp", "gather", 8 * ms, 1 * ms, "d0")]
    host = [(tr.WINDOW, 0.0, 10 * ms),
            ("chipbench.dispatch", 0.0, 0.5 * ms),
            ("chipbench.wait", 0.5 * ms, 10 * ms)]
    return tr.Trace((0.0, 10 * ms), ops, host, ["d0"])


def test_busy_times_and_gaps():
    t = _synthetic()
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_s() == pytest.approx(0.006)         # [1,6] and [8,9] ms
    assert t.time_s(["fp"], kind="pallas") == pytest.approx(0.004)
    assert t.time_s(["fp", "bp"], not_kind="pallas") == pytest.approx(0.004)
    assert t.top_ops(2) == [["fp/pallas/custom-call.2", pytest.approx(0.004)],
                            ["fp/other/transpose.4", pytest.approx(0.003)]]
    gaps = t.idle_gaps()
    assert [g[0] for g in gaps] == ["wait", "dispatch", "wait"]
    assert [g[1] for g in gaps] == pytest.approx([0.002, 0.001, 0.001])


def test_metric_readers():
    t = _synthetic()
    work = {"fp": {"flops": 0.0, "bytes": 8.19e5},
            "bp": {"flops": 0.0, "bytes": 8.19e5}}
    r = tr.Reading(t, 2, work, {"flops": 1e12, "hbm_bytes_per_s": 819e9})
    # 1 us of necessary bytes over 2 ms of FP kernel per step.
    assert cells.reader("fp_roofline")(r) == pytest.approx(0.05)
    assert cells.reader("bp_roofline")(r) is None      # no BP kernel ran
    assert cells.reader("xla_ms.step")(r) == pytest.approx(2.0)
    assert cells.reader("idle_share.step")(r) == pytest.approx(40.0)
    assert cells.reader("gather_ms.recon")(r) is None  # no fbp scope


@pytest.mark.parametrize("name", ["idle_share.step", "idle_share.recon"])
def test_split_metric_read_by_its_stem(name):
    """``idle_share.<part>`` has no file of its own: ``idle_share.py``
    reads it, whichever end-to-end metric the part moves."""
    assert not (cells.HERE / "metrics" / f"{name}.py").exists()
    r = tr.Reading(_synthetic(), 2, {}, {})
    assert cells.reader(name)(r) == pytest.approx(40.0)


DATA = pathlib.Path(__file__).parent / "data"


def _recorded(cell: str, scopes):
    """A small trace recorded on one v5e through the harness's window: one
    step of the cell's program at 64x64x128, 16 views."""
    import gzip
    hlo = tr.hlo_index(gzip.open(DATA / f"{cell}.small.hlo.txt.gz",
                                 "rt").read())
    return tr.read(DATA / f"{cell}.small.xplane.pb.gz", hlo, scopes), hlo


def test_recorded_projector_step():
    t, hlo = _recorded("par512.fp_bp", ("fp", "residual", "bp"))
    assert t.devices == ["/device:TPU:0"]
    assert all(o.name in hlo for o in t.ops)
    assert t.window_s == pytest.approx(1.902329e-3)
    assert t.busy_s() == pytest.approx(8.01923e-4)
    assert t.time_s(["fp"], kind="pallas") == pytest.approx(5.14649e-4)
    assert t.time_s(["bp"], kind="pallas") == pytest.approx(2.72532e-4)
    assert t.time_s(["residual"]) == pytest.approx(1.66e-7)
    assert t.top_ops(1) == [["fp/pallas/fp.2", pytest.approx(3.52262e-4)]]
    gaps = t.idle_gaps()
    assert gaps[0] == ["wait", pytest.approx(1.100362e-3)]
    r = tr.Reading(t, 1, {"fp": {"flops": 0.0, "bytes": 819e3}},
                   {"flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert cells.reader("fp_roofline")(r) == pytest.approx(
        100 * 1e-6 / 5.14649e-4)
    assert cells.reader("xla_ms.step")(r) == pytest.approx(
        1e3 * (3.57e-7 + 6.741e-6 + 3.62e-7 + 7.043e-6))


def test_recorded_fbp():
    t, _ = _recorded("par512.fbp", ("fbp",))
    assert t.busy_s() == pytest.approx(5.22911e-4)
    assert t.time_s(["fbp"], kind="gather") == pytest.approx(2.05971e-4)
    assert t.time_s(["fbp"], kind="pallas") == 0.0
    assert any(o.kind == "loop" for o in t.ops)   # the scan's while op
    r = tr.Reading(t, 1, {}, {})
    assert cells.reader("gather_ms.recon")(r) == pytest.approx(0.205971)
    assert cells.reader("idle_share.recon")(r) == pytest.approx(
        100 * (1 - 5.22911e-4 / 1.522429e-3))
    assert cells.reader("fp_roofline")(r) is None
