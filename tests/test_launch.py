"""Launcher integration: train loop + checkpoint resume + failure injection,
and the dry-run cell machinery on the local mesh (CI-scale)."""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat, configs
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import make_local_mesh
from repro.launch.train import train_loop
from repro.runtime import checkpoint as CKPT


def test_train_loop_checkpoint_resume(tmp_path):
    cfg = dataclasses.replace(configs.get_smoke("tinyllama_1_1b"),
                              grad_accum=1)
    mesh = make_local_mesh()
    # run 1: 6 steps, checkpoint every 3
    pipe = TokenPipeline(cfg.vocab_size, 32, 4)
    _, losses_a = train_loop(cfg, mesh, pipe, steps=6,
                             ckpt_dir=str(tmp_path), ckpt_every=3,
                             log_every=100)
    assert CKPT.latest_step(str(tmp_path)) == 6
    # run 2 from scratch to 3, then resume 3->6: the resumed loss trajectory
    # must match run 1 exactly (deterministic data + exact state restore)
    d2 = tmp_path / "two"
    pipe2 = TokenPipeline(cfg.vocab_size, 32, 4)
    train_loop(cfg, mesh, pipe2, steps=3, ckpt_dir=str(d2), ckpt_every=3,
               log_every=100)
    pipe3 = TokenPipeline(cfg.vocab_size, 32, 4)
    _, losses_b = train_loop(cfg, mesh, pipe3, steps=6, ckpt_dir=str(d2),
                             ckpt_every=3, log_every=100)
    np.testing.assert_allclose(losses_a[3:], losses_b, rtol=1e-4)


@pytest.mark.slow
def test_supervisor_recovers_from_injected_failure(tmp_path):
    """Full driver subprocess: crash at step 10, auto-restart, finish."""
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-0.6b",
           "--smoke", "--steps", "16", "--batch", "4", "--seq", "32",
           "--ckpt-dir", str(tmp_path), "--fail-at", "10", "--ckpt-every", "4"]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done." in out.stdout
    assert CKPT.latest_step(str(tmp_path)) == 16


def test_dryrun_cell_machinery_local():
    """lower_cell logic on a 1-device mesh with a reduced config — validates
    the sharding/lowering plumbing the 512-device dry-run uses."""
    from repro.launch import sharding
    from repro.launch.steps import make_serve_step, make_train_step
    from repro.models import model as MD
    from repro.optim import adamw, constant

    cfg = dataclasses.replace(configs.get_smoke("qwen3_0_6b"), grad_accum=1)
    mesh = make_local_mesh()
    ac = sharding.make_ac(mesh, cfg)
    aparams = MD.abstract_params(cfg)
    pshard = sharding.param_shardings(cfg, aparams, mesh)
    opt = adamw(constant(1e-3))
    aopt = jax.eval_shape(opt.init, aparams)
    step = make_train_step(cfg, opt, ac)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
    with mesh:
        lowered = jax.jit(step, in_shardings=(pshard, None, None)).lower(
            aparams, aopt, batch)
        compiled = lowered.compile()
    assert compat.cost_analysis_dict(compiled).get("flops", 0) > 0
    # decode path
    serve = make_serve_step(cfg, ac)
    cache = MD.cache_shapes(cfg, 4, 64)
    cshard = sharding.cache_shardings(cache, mesh)
    with mesh:
        lowered = jax.jit(serve, in_shardings=(pshard, cshard, None, None)) \
            .lower(aparams, cache,
                   jax.ShapeDtypeStruct((4,), jnp.int32),
                   jax.ShapeDtypeStruct((), jnp.int32))
        lowered.compile()


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.roofline import collective_bytes_from_hlo
    hlo = """
ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %x = f32[8,128]{1,0} all-reduce(f32[8,128] %a), replica_groups={{0,1,2,3}}
  %y = bf16[4,256]{1,0} all-gather(bf16[4,64] %b), replica_groups=[2,8]
  ROOT %z = f32[8,128]{1,0} collective-permute(f32[8,128] %x)
}
"""
    out = collective_bytes_from_hlo(hlo, n_devices=8)
    assert out["op_counts"] == {"all-reduce": 1, "all-gather": 1,
                                "collective-permute": 1}
    # all-reduce: 8*128*4 bytes * 2*(4-1)/4
    assert abs(out["per_op_bytes"]["all-reduce"] - 8 * 128 * 4 * 1.5) < 1
    # all-gather: result 4*256*2 bytes * (8-1)/8
    assert abs(out["per_op_bytes"]["all-gather"] - 4 * 256 * 2 * 7 / 8) < 1


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the table for the device's kind; an unknown device
    is an error, never the v5e's numbers by default."""
    from repro.launch import roofline
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    t = roofline.terms(197e12, 0.0, 0.0, 1, "TPU v5 lite")
    assert t["bound"] == "compute" and abs(t["step_s"] - 1.0) < 1e-12
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
    with pytest.raises(ValueError):
        roofline.terms(1.0, 1.0, 0.0, 1, "TPU v9 imaginary")


@pytest.mark.slow
def test_elastic_remesh_plan_compiles(tmp_path):
    """Lose a pod's worth of chips -> plan_remesh shrinks the data axis ->
    the SAME training program lowers+compiles on the surviving mesh.
    (Subprocess: needs its own forced host device count.)

    The plan logic is asserted at full scale (160 chips -> (8, 16)); the
    compile proof runs a *smaller* remesh scenario (40 chips -> (2, 16), 32
    forced host devices) with the layer count shrunk via cfg_overrides —
    the 128-device full-model compile exceeded the subprocess timeout on
    2-vCPU CI-class containers (see CHANGES.md PR 4), and neither the mesh
    logic nor the sharding validity depends on the layer count."""
    from repro.runtime.fault import plan_remesh
    assert plan_remesh(n_healthy_chips=160, model_axis=16, pods=1) == (8, 16)
    new_shape = plan_remesh(n_healthy_chips=40, model_axis=16, pods=1)
    assert new_shape == (2, 16)       # 32 of the surviving 40 chips
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
import jax
# Initialize the backend *before* importing dryrun: its module-level
# XLA_FLAGS write forces 512 host devices for the CLI use case, and a
# 512-device CPU client is most of what made this test time out.
assert jax.device_count() == 32
from repro import compat
from repro.launch.dryrun import lower_cell
mesh = compat.make_mesh({new_shape!r}, ("data", "model"))
lowered, reason = lower_cell("qwen3-0.6b", "train_4k", mesh,
                             cfg_overrides={{"n_layers": 2}})
assert reason is None
lowered.compile()
print("REMESH_OK")
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=900,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "REMESH_OK" in out.stdout
