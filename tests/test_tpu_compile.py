"""The matched Pallas pairs compile for a TPU v5e at the paper's widths.

Every other test runs the kernels in interpret mode, which accepts layouts
the chip's compiler (Mosaic) refuses: a 1 in a block's second-minor place,
a float iota, a dynamic bf16 window start it cannot prove tile-aligned.
These tests compile FP and BP of all five pairs (parallel, fan, exact cone,
packed cone, modular/helical) for one chip of a described, not attached,
``v5e:2x2`` topology, unbatched and batched, in f32 and bf16, with the TPU
heuristic kernel configs.  Nothing runs; a refusal fails the test.
The bf16 cases compile under ``jax.default_matmul_precision("highest")``,
the setting an oracle comparison runs under: a kernel that leaves its MXU
precision implicit then asks Mosaic for an f32 contraction of bf16 tiles.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and it keeps it until it
exits, so every compile happens in this test process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.leap_ct import limited_angle_geometry, table1_geometries
from repro.core import VolumeGeometry, parallel_beam
from repro.core.spec import ProjectorSpec
from repro.kernels import ops, tune
from repro.launch.ct_train import TrainConfig, build_geometry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    saved = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"       # no compiler logs under /tmp
    cache = jax.config.jax_enable_compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache)
    if saved is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = saved


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels for the chip (not the interpreter) with the TPU
    heuristic configs (view blocking ba=8, bab=4; stripe reuse over the
    whole gathered line in the parallel BP, bs=2 in the fan BPs)."""
    monkeypatch.setattr(tune, "_interpret", lambda: False)
    monkeypatch.setattr(tune, "_on_tpu", lambda: True)


def _t1(name):
    return lambda: table1_geometries()[name]


def _train(geometry, **kw):
    return lambda: build_geometry(TrainConfig(geometry=geometry, n=512, **kw))


# name -> (geometry, leading batch, kernel mode, compute dtype)
CASES = {
    "parallel-512-180": (_t1("parallel_512_180"), 0, "exact", None),
    "parallel-512-180-bf16": (_t1("parallel_512_180"), 0, "exact",
                              "bfloat16"),
    "cone-exact-512-180-bf16": (_t1("cone_512_180"), 0, "exact",
                                "bfloat16"),
    # The z-slab each chip of the four-chip 1024^3/720 deployment runs.
    "parallel-1024-slab-720": (lambda: parallel_beam(
        720, 256, 1536, VolumeGeometry(1024, 1024, 256)), 0, "exact", None),
    "parallel-limited-angle-b8": (limited_angle_geometry, 8, "exact", None),
    "fan-512-b8": (_train("sparse_fan"), 8, "exact", None),
    "cone-exact-512-180": (_t1("cone_512_180"), 0, "exact", None),
    "cone-exact-512-180-b2": (_t1("cone_512_180"), 2, "exact", None),
    "cone-packed-512-180": (_t1("cone_512_180"), 0, "packed", None),
    "cone-packed-512-180-b2": (_t1("cone_512_180"), 2, "packed", None),
    "helical-512-nz64": (_train("helical", nz=64), 0, "exact", None),
    "helical-512-nz64-b2": (_train("helical", nz=64), 2, "exact", None),
}


@pytest.mark.parametrize("direction", ["fp", "bp"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_pair_compiles_for_v5e(case, direction, one_chip, mosaic):
    make, batch, mode, cdt = CASES[case]
    geom = make()
    spec = ProjectorSpec(geom, backend="pallas", mode=mode,
                         compute_dtype=cdt)
    assert ops.resolve_mode(spec) == mode
    lead = (batch,) if batch else ()
    if direction == "fp":
        fn, shape = (lambda x: ops.forward_project(x, spec)), geom.vol.shape
    else:
        fn, shape = (lambda p: ops.back_project(p, spec)), geom.sino_shape
    arg = jax.ShapeDtypeStruct(lead + tuple(shape), jnp.float32,
                               sharding=one_chip)
    with jax.default_matmul_precision("highest" if cdt else None):
        compiled = jax.jit(fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The harness's own scope names: a program scope must never equal one, or
# a reader matching whole op_name parts would take it for the harness's.
HARNESS_SCOPES = ("fp", "residual", "bp", "fbp")
SCOPED = {
    "parallel": {"geom_type": "parallel",
                 "volume": {"nx": 8, "ny": 8, "nz": 8},
                 "n_angles": 8, "n_rows": 8, "n_cols": 16,
                 "angular_range": 180.0},
    "cone": {"geom_type": "cone", "volume": {"nx": 8, "ny": 8, "nz": 8},
             "n_angles": 2, "n_rows": 8, "n_cols": 16,
             "pixel_width": 2.0, "pixel_height": 2.0, "sod": 32.0,
             "sdd": 64.0, "detector_type": "flat",
             "angles": [0.6283185307179586, 1.2566370614359172]},
}
STAGES = {
    "parallel": {f"sf_par_{d}_{s}" for d in ("fp", "bp")
                 for s in ("axial", "pre", "g0", "g1", "post")},
    "cone": {f"sf_cone_{d}_{s}" for d in ("fp", "bp")
             for s in ("pre", "g0", "g1", "post")},
    "fbp": {"fbp_filter", "fbp_interp", "fbp_zcontract", "fbp_accum"},
    # The sharded pair's stages: the toy cone on a (2, 2) mesh of the four
    # described chips, two angle shards and two z-slabs with a halo.
    "sharded": {"dist_local_fp", "dist_local_bp", "dist_reduce",
                "dist_halo_exchange", "dist_halo_reduce"},
}


@pytest.mark.parametrize("kind", sorted(STAGES))
def test_device_scopes_name_the_stages(kind, topo, one_chip, mosaic):
    """The chip's compiled program of a toy projector step (under the
    harness's scopes ``fp``, ``residual``, ``bp``), of the same step through
    the sharded pair on four chips, and of a toy FBP (under ``fbp``)
    carries each program stage's scope in its ops' ``op_name``, names each
    kernel by its pair, direction and view group, and has no program scope
    equal to a harness scope."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.core.distributed import DistributedProjector, suggest_halo
    from repro.core.fbp import fbp
    from repro.core.geometry import from_config
    from repro.core.projector import Projector
    from repro.core.spec import ShardSpec
    geom = from_config(SCOPED[{"fbp": "parallel", "sharded": "cone"}.get(
        kind, kind)])
    shardings = [one_chip, one_chip]
    if kind == "fbp":
        def fn(p):
            with jax.named_scope("fbp"):
                return fbp(p, geom)
        args = (geom.sino_shape,)
    else:
        spec = ProjectorSpec(geom, backend="pallas", mode="exact")
        if kind == "sharded":
            mesh = compat.make_mesh((2, 2), ("data", "model"),
                                    devices=topo.devices)
            shard = ShardSpec(("data", "model"), angle_shards=2, z_shards=2,
                              halo=suggest_halo(geom, 2))
            proj = DistributedProjector(spec.replace(shard=shard), mesh)
            shardings = [NamedSharding(mesh, P(None, None, "model")),
                         NamedSharding(mesh, P("data", "model", None))]
        else:
            proj = Projector(spec)

        def fn(x, b):
            with jax.named_scope("fp"):
                s = proj(x)
            with jax.named_scope("residual"):
                r = s - b
            with jax.named_scope("bp"):
                return r, proj.T(r)
        args = (geom.vol.shape, geom.sino_shape)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sh)
            for s, sh in zip(args, shardings)]
    with jax.default_matmul_precision("highest"):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    parts = set()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for op_name in (n for joined in names for n in joined.split(";")):
        split = op_name.split("/")
        first = [i for i, p in enumerate(split) if p in HARNESS_SCOPES]
        below = split[first[0] + 1:] if first else []   # else an argument
        assert not set(below) & set(HARNESS_SCOPES), op_name
        parts.update(below)
    assert STAGES[kind] <= parts, STAGES[kind] - parts
    kernels = re.findall(r'^\s*(?:ROOT\s+)?%?([\w\-]+)\.\d+ = \S+ custom-call'
                         r'.*custom_call_target="tpu_custom_call"', hlo,
                         re.M)
    if kind == "sharded":       # each shard's branch has its own kernels
        assert kernels and set(kernels) <= STAGES["cone"], kernels
    else:
        assert sorted(kernels) == sorted(s for s in STAGES[kind]
                                         if s[-2:] in ("g0", "g1"))
