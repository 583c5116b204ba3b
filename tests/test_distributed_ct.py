"""Distributed CT projection (shard_map over angles / z-slabs).

Single-device CI runs the (1, 1)-mesh paths (shard_map wiring, psum,
ppermute self-loops, validation, the legacy shim) plus everything that is
pure host code (``suggest_halo``).  The multi-shard numerics — halo
exchange vs a numpy oracle, the three sharded layouts vs local ops,
adjointness, the sliding-z helical capacity proof — run under the CI
``distributed`` leg with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
and are skip-gated on device count here."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import (Projector, ProjectorSpec, ShardSpec, VolumeGeometry,
                        cone_beam, helical_beam, parallel_beam)
from repro.core.distributed import (DistributedProjector, _angle_chunks,
                                    distribute, halo_exchange_z,
                                    halo_reduce_z, make_distributed_projector,
                                    suggest_halo)
from repro.kernels import ops
from repro.recon.result import as_projector
from repro.recon.sirt import sirt

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh():
    return compat.make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def mesh42():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    return compat.make_mesh((4, 2), ("data", "model"))


@pytest.fixture(scope="module")
def mesh24():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    return compat.make_mesh((2, 4), ("data", "model"))


def _dot_rel(dp, geom, seed=0):
    """Conditioning-aware adjointness error: |<Ax,y> - <x,A^T y>| over the
    term mass sum|Ax*y| (a raw /|<Ax,y>| blows up when the random dot
    cancels)."""
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, geom.vol.shape)
    y = jax.random.normal(ky, geom.sino_shape)
    Ax = dp(dp.shard_volume(x))
    ATy = dp.T(dp.shard_sino(y))
    lhs = jnp.vdot(Ax, y)
    rhs = jnp.vdot(x, ATy)
    mass = float(jnp.sum(jnp.abs(Ax * y))) + 1e-12
    return abs(float(lhs - rhs)) / mass


def _vs_local(dp, geom, tol=2e-5, seed=0):
    fp, bp = ops.get_ops(dp.spec.replace(shard=None))
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, geom.vol.shape)
    y = jax.random.normal(ky, geom.sino_shape)
    np.testing.assert_allclose(np.asarray(dp(dp.shard_volume(x))),
                               np.asarray(fp(x)), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(dp.T(dp.shard_sino(y))),
                               np.asarray(bp(y)), rtol=tol,
                               atol=tol * float(jnp.max(jnp.abs(bp(y)))))


# --------------------------------------------------------------------------- #
# Single-device paths (tier-1)
# --------------------------------------------------------------------------- #
def test_legacy_factory_matches_local(mesh):
    vol = VolumeGeometry(24, 24, 4)
    g = parallel_beam(8, 4, 36, vol)
    fp, bp, shard_v, shard_s = make_distributed_projector(
        g, mesh, angle_axis="data", z_axis="model")
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    proj = Projector(ProjectorSpec(g))
    np.testing.assert_allclose(np.asarray(fp(shard_v(f))),
                               np.asarray(proj(f)), rtol=1e-5, atol=1e-5)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    np.testing.assert_allclose(np.asarray(bp(shard_s(y))),
                               np.asarray(proj.T(y)), rtol=1e-5, atol=1e-5)
    # the spec_vol/spec_sino attribute-stuffing hack is gone
    assert not hasattr(fp, "spec_vol") and not hasattr(fp, "spec_sino")


def test_legacy_factory_warns_once(mesh):
    from repro.core.spec import reset_legacy_warnings
    reset_legacy_warnings()
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(4, 4, 24, vol)
    with pytest.warns(DeprecationWarning):
        make_distributed_projector(g, mesh)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        make_distributed_projector(g, mesh)   # second call: silent


def test_legacy_factory_cone_zslab_still_not_implemented(mesh):
    vol = VolumeGeometry(16, 16, 4)
    g = cone_beam(4, 4, 24, vol, sod=60.0, sdd=80.0)
    with pytest.raises(NotImplementedError, match="halo"):
        make_distributed_projector(g, mesh, z_axis="model")


def test_distributed_pair_matched(mesh):
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(4, 4, 24, vol)
    dp = distribute(ProjectorSpec(g), mesh, z_axis="model")
    assert _dot_rel(dp, g) < 1e-6


def test_angle_chunking_requires_divisibility():
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(5, 4, 24, vol)
    with pytest.raises(ValueError, match="divisible"):
        _angle_chunks(g, 2)


def test_halo_exchange_validates_halo_width():
    f = jnp.zeros((4, 4, 4))
    with pytest.raises(ValueError, match="smaller than the local slab"):
        halo_exchange_z(f, "model", 4)
    with pytest.raises(ValueError, match=">= 0"):
        halo_exchange_z(f, "model", -1)
    with pytest.raises(ValueError, match="extended slab"):
        halo_reduce_z(f, "model", 2)


def test_halo_exchange_identity_on_single_shard(mesh):
    f = jax.random.normal(jax.random.PRNGKey(0), (8, 8, 6))

    @partial(compat.shard_map, mesh=mesh,
             in_specs=(P(None, None, "model"),),
             out_specs=P(None, None, "model"), check_vma=False)
    def run(fl):
        return halo_exchange_z(fl, "model", 2)

    out = run(f)
    # single shard: both halos are fleet edges -> zeros
    assert out.shape == (8, 8, 10)
    np.testing.assert_array_equal(np.asarray(out[:, :, :2]), 0.0)
    np.testing.assert_allclose(np.asarray(out[:, :, 2:8]), np.asarray(f))
    np.testing.assert_array_equal(np.asarray(out[:, :, 8:]), 0.0)


def test_ops_cache_rejects_sharded_spec():
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(4, 4, 24, vol)
    spec = ProjectorSpec(g, shard=ShardSpec(("data", None)))
    with pytest.raises(ValueError, match="DistributedProjector"):
        ops.get_ops(spec)


def test_as_projector_accepts_distributed(mesh):
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(4, 4, 24, vol)
    dp = distribute(ProjectorSpec(g), mesh)
    assert as_projector(dp) is dp
    with pytest.raises(ValueError, match="mesh"):
        as_projector(ProjectorSpec(g, shard=ShardSpec(("data", None))))


def test_distributed_projector_validation(mesh):
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(4, 4, 24, vol)
    with pytest.raises(TypeError, match="ProjectorSpec"):
        DistributedProjector(g, mesh)
    with pytest.raises(ValueError, match="ShardSpec"):
        DistributedProjector(ProjectorSpec(g), mesh)
    # shard layout must match the mesh
    spec = ProjectorSpec(g, shard=ShardSpec(("data", None), angle_shards=4))
    with pytest.raises(ValueError, match="mesh axis"):
        DistributedProjector(spec, mesh)
    spec = ProjectorSpec(g, shard=ShardSpec(("rows", None)))
    with pytest.raises(ValueError, match="no axis"):
        DistributedProjector(spec, mesh)
    with pytest.raises(TypeError, match="not both"):
        distribute(ProjectorSpec(g, shard=ShardSpec(("data", None))),
                   mesh, z_axis="model")


def test_suggest_halo():
    vol = VolumeGeometry(24, 24, 8)
    # parallel/fan: slabs exactly independent
    assert suggest_halo(parallel_beam(8, 8, 36, vol), 2) == 0
    gc = cone_beam(8, 8, 36, vol, sod=60.0, sdd=80.0)
    h = suggest_halo(gc, 2)
    assert 1 <= h < 4          # small cone angle: a sliver, not a slab
    tall = VolumeGeometry(24, 24, 32)
    gh = helical_beam(n_turns=4, pitch=8.0, n_angles=32, n_rows=6, n_cols=32,
                      vol=tall, sod=60.0, sdd=80.0)
    h = suggest_halo(gh, 4)
    assert 1 <= h < 8          # halo < nz_local: the pipeline is feasible
    with pytest.raises(ValueError, match="divisible"):
        suggest_halo(gh, 3)
    assert suggest_halo(gh, 1) == 0


def test_sirt_bit_parity_single_device_mesh(mesh):
    # On a (1,1) mesh with the synchronous-psum schedule the distributed
    # program runs the *same* cached local ops — sirt must be bit-exact
    # against the plain Projector run.
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(8, 4, 24, vol)
    spec = ProjectorSpec(g)
    dp = distribute(spec, mesh, comm="psum")
    f = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), vol.shape))
    y = Projector(spec)(f)
    a = sirt(dp, y, n_iters=4)
    b = sirt(spec, y, n_iters=4)
    np.testing.assert_array_equal(np.asarray(a.image), np.asarray(b.image))
    np.testing.assert_array_equal(np.asarray(a.residual_history),
                                  np.asarray(b.residual_history))


# --------------------------------------------------------------------------- #
# Multi-shard numerics (CI `distributed` leg: 8 forced host devices)
# --------------------------------------------------------------------------- #
@needs8
def test_halo_exchange_matches_numpy_oracle(mesh24):
    nz, shards, halo = 16, 4, 2
    nzl = nz // shards
    f = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (6, 6, nz)))

    @partial(compat.shard_map, mesh=mesh24,
             in_specs=(P(None, None, "model"),),
             out_specs=P(None, None, "model"), check_vma=False)
    def run(fl):
        return halo_exchange_z(fl, "model", halo)

    out = np.asarray(run(jnp.asarray(f)))
    assert out.shape == (6, 6, shards * (nzl + 2 * halo))
    padded = np.concatenate([np.zeros((6, 6, halo)), f,
                             np.zeros((6, 6, halo))], axis=2)
    for k in range(shards):
        got = out[:, :, k * (nzl + 2 * halo):(k + 1) * (nzl + 2 * halo)]
        want = padded[:, :, k * nzl:k * nzl + nzl + 2 * halo]
        np.testing.assert_allclose(got, want, err_msg=f"shard {k}")


@needs8
def test_halo_reduce_is_adjoint_of_exchange(mesh24):
    nz, halo = 16, 2
    ext = nz + 2 * halo * 4
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 5, nz))
    y = jax.random.normal(jax.random.PRNGKey(1), (5, 5, ext))

    @partial(compat.shard_map, mesh=mesh24,
             in_specs=(P(None, None, "model"),),
             out_specs=P(None, None, "model"), check_vma=False)
    def E(fl):
        return halo_exchange_z(fl, "model", halo)

    @partial(compat.shard_map, mesh=mesh24,
             in_specs=(P(None, None, "model"),),
             out_specs=P(None, None, "model"), check_vma=False)
    def ET(gl):
        return halo_reduce_z(gl, "model", halo)

    lhs = float(jnp.vdot(E(x), y))
    rhs = float(jnp.vdot(x, ET(y)))
    assert abs(lhs - rhs) / (abs(lhs) + 1e-12) < 1e-5


@needs8
def test_angle_sharded_matches_local_and_adjoint(mesh42):
    vol = VolumeGeometry(24, 24, 8)
    g = parallel_beam(16, 8, 32, vol)
    dp = distribute(ProjectorSpec(g), mesh42)
    _vs_local(dp, g)
    assert _dot_rel(dp, g) < 1e-6


@needs8
def test_parallel_zslab_matches_local_and_adjoint(mesh42):
    vol = VolumeGeometry(24, 24, 8)
    g = parallel_beam(16, 8, 32, vol)
    dp = distribute(ProjectorSpec(g), mesh42, z_axis="model")
    assert dp.shard.halo == 0
    _vs_local(dp, g)
    assert _dot_rel(dp, g) < 1e-6


@needs8
def test_cone_halo_zslab_matches_local_and_adjoint(mesh42):
    vol = VolumeGeometry(24, 24, 8)
    g = cone_beam(16, 8, 32, vol, sod=60.0, sdd=80.0)
    dp = distribute(ProjectorSpec(g), mesh42, z_axis="model")
    assert dp.shard.halo >= 1          # halo path actually exercised
    _vs_local(dp, g)
    assert _dot_rel(dp, g) < 1e-6


@needs8
def test_cone_undersized_halo_rejected(mesh42):
    vol = VolumeGeometry(24, 24, 8)
    g = cone_beam(16, 8, 32, vol, sod=60.0, sdd=80.0)
    with pytest.raises(ValueError, match="halo"):
        distribute(ProjectorSpec(g), mesh42, z_axis="model", halo=0)


@needs8
def test_helical_sliding_z_capacity_and_adjoint(mesh24):
    # The long-object proof: with z_shards=4 each device materializes an
    # (nzl + 2*halo)-deep slab that is strictly smaller than the full
    # volume — a volume that exceeds one device's slab budget reconstructs
    # anyway.
    tall = VolumeGeometry(24, 24, 32)
    g = helical_beam(n_turns=4, pitch=8.0, n_angles=32, n_rows=6, n_cols=32,
                     vol=tall, sod=60.0, sdd=80.0)
    dp = distribute(ProjectorSpec(g), mesh24, z_axis="model")
    nzl = tall.nz // dp.shard.z_shards
    assert nzl + 2 * dp.shard.halo < tall.nz
    _vs_local(dp, g)
    assert _dot_rel(dp, g) < 1e-6


@needs8
def test_helical_sliding_z_sirt_end_to_end(mesh24):
    tall = VolumeGeometry(24, 24, 32)
    g = helical_beam(n_turns=4, pitch=8.0, n_angles=32, n_rows=6, n_cols=32,
                     vol=tall, sod=60.0, sdd=80.0)
    spec = ProjectorSpec(g)
    dp = distribute(spec, mesh24, z_axis="model")
    f = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), tall.shape))
    y = dp(dp.shard_volume(f))
    res = sirt(dp, y, n_iters=12)
    hist = np.asarray(res.residual_history)
    assert hist[-1] < 0.25 * hist[0]   # the mesh loop actually converges
    # parity with the single-device solve
    ref = sirt(spec, y, n_iters=12)
    np.testing.assert_allclose(np.asarray(res.image), np.asarray(ref.image),
                               rtol=1e-4, atol=1e-4)


@needs8
def test_overlap_comm_matches_psum(mesh24):
    tall = VolumeGeometry(24, 24, 32)
    g = helical_beam(n_turns=4, pitch=8.0, n_angles=32, n_rows=6, n_cols=32,
                     vol=tall, sod=60.0, sdd=80.0)
    spec = ProjectorSpec(g)
    over = distribute(spec, mesh24, z_axis="model", comm="overlap")
    sync = distribute(spec, mesh24, z_axis="model", comm="psum")
    y = jax.random.normal(jax.random.PRNGKey(0), g.sino_shape)
    np.testing.assert_allclose(np.asarray(over.T(over.shard_sino(y))),
                               np.asarray(sync.T(sync.shard_sino(y))),
                               rtol=1e-5, atol=1e-5)


@needs8
def test_dp_train_step_decreases_loss(mesh42):
    from repro.launch.train import make_ct_dp_train_step
    vol = VolumeGeometry(16, 16, 8)
    g = parallel_beam(16, 8, 24, vol)
    spec = ProjectorSpec(g)

    def apply_fn(params, y):
        return jnp.broadcast_to(params["vol"], (y.shape[0],) + vol.shape)

    step = make_ct_dp_train_step(spec, mesh42, apply_fn, lr=5e-3,
                                 axis="data")
    truth = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), vol.shape))
    y1 = Projector(spec)(truth)
    yb = jnp.stack([y1] * 8)
    params = {"vol": jnp.zeros(vol.shape)}
    losses = []
    for _ in range(5):
        params, loss = step(params, yb)
        losses.append(float(loss))
    # mechanics test, not a convergence benchmark: grads flow through the
    # matched pair, the pmean syncs shards, and every step improves
    assert all(b < a for a, b in zip(losses, losses[1:]))
    assert losses[-1] < losses[0]


# --------------------------------------------------------------------------- #
# Four devices, in a child process (tier-1)
# --------------------------------------------------------------------------- #
def _four_device_child():
    """Runs in a child process with four host devices: builds and traces the
    pairs the tests below read, and prints what they read as JSON."""
    import json
    from repro.runtime import obs
    mesh = compat.make_mesh((1, 4), ("data", "model"))
    out = {"devices": jax.device_count()}

    obs._REC = obs.Recorder()
    vol = VolumeGeometry(8, 8, 8)
    g = parallel_beam(16, 8, 16, vol)

    def pair(**kw):
        shard = ShardSpec(("data", "model"), angle_shards=1, z_shards=4,
                          **kw)
        return DistributedProjector(
            ProjectorSpec(g, backend="pallas", shard=shard), mesh)

    one = pair()
    out["one_local_ops"] = obs.counter("distributed.local_ops")
    four = pair(comm_blocks=4)
    out["four_local_ops"] = obs.counter("distributed.local_ops") - \
        out["one_local_ops"]
    out["comm_blocks"] = [one.comm_blocks, four.comm_blocks]
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, vol.shape)
    y = jax.random.normal(ky, g.sino_shape)
    s1, s4 = one(one.shard_volume(x)), four(four.shard_volume(x))
    b1, b4 = one.T(one.shard_sino(y)), four.T(four.shard_sino(y))
    out["fp_rel"] = float(jnp.max(jnp.abs(s1 - s4)) / jnp.max(jnp.abs(s1)))
    out["bp_rel"] = float(jnp.max(jnp.abs(b1 - b4)) / jnp.max(jnp.abs(b1)))
    fp_ref, bp_ref = ops.get_ops(one.spec.replace(shard=None))
    out["bp_vs_local"] = float(jnp.max(jnp.abs(b1 - bp_ref(y)))
                               / jnp.max(jnp.abs(b1)))
    snap = obs.snapshot()
    out["par_collectives"] = snap["counters"].get("distributed.collectives")
    out["spans"] = {k: v["count"] for k, v in snap["spans"].items()}

    obs._REC = obs.Recorder()
    deep = VolumeGeometry(24, 24, 32)
    gc = cone_beam(8, 40, 32, deep, sod=60.0, sdd=80.0)
    halo = suggest_halo(gc, 4)
    dc = DistributedProjector(ProjectorSpec(gc, shard=ShardSpec(
        ("data", "model"), angle_shards=1, z_shards=4, halo=halo)), mesh)
    f = jax.random.normal(kx, deep.shape)
    jax.block_until_ready(dc.T(dc(dc.shard_volume(f))))
    out["cone_halo"] = halo
    out["cone_collectives"] = obs.counter("distributed.collectives")
    print(json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def four_devices():
    import json
    import os
    import pathlib
    import subprocess
    import sys
    here = pathlib.Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(here.parent / "src"),
                                           str(here)]))
    code = "from test_distributed_ct import _four_device_child as c; c()"
    out = subprocess.run([sys.executable, "-c", code], cwd=here.parent,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_one_angle_shard_builds_one_bp_bundle_and_no_psum(four_devices):
    """With one angle shard there is nothing to reduce: one comm block (the
    FP and BP share the chunk's bundle), no collective traced, and the
    result equals the four-block schedule to float32 rounding."""
    r = four_devices
    assert r["devices"] == 4
    assert r["comm_blocks"] == [1, 4]
    assert r["one_local_ops"] == 1           # one chunk, one bundle
    assert r["four_local_ops"] == 1 + 4      # the FP's, and one per block
    assert r["par_collectives"] == 0
    assert r["fp_rel"] == 0.0
    assert r["bp_rel"] < 1e-6
    assert r["bp_vs_local"] < 1e-5


def test_cone_halo_zslab_counts_its_ppermutes(four_devices):
    r = four_devices
    assert 1 <= r["cone_halo"] < 32 // 4
    assert r["cone_collectives"] == 4        # exchange and reduce: 2 each


def test_distributed_spans_recorded(four_devices):
    spans = four_devices["spans"]
    assert spans["distributed.build"] == 2
    for name in ("distributed.fp", "distributed.bp"):
        assert spans[name] >= 2, spans
