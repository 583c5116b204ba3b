"""ProjectorSpec: validation, content identity, cache keys, the one
refusal of every entry point, and the stable-geometry-hash bugfix."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Projector, ProjectorSpec, VolumeGeometry, cone_beam,
                        fan_beam, from_config, helical_beam, modular_beam,
                        parallel_beam)
from repro.core.spec import ShardSpec
from repro.kernels import ops
from repro.kernels.tune import KernelConfig


@pytest.fixture()
def geom():
    return parallel_beam(12, 1, 16, VolumeGeometry(8, 8, 1))


def _geoms():
    vol = VolumeGeometry(8, 8, 4)
    ang = np.linspace(0, 2 * np.pi, 6, endpoint=False)
    src = np.stack([100 * np.cos(ang), 100 * np.sin(ang),
                    np.zeros_like(ang)], -1)
    eu = np.stack([-np.sin(ang), np.cos(ang), np.zeros_like(ang)], -1)
    ev = np.tile(np.array([0.0, 0.0, 1.0]), (len(ang), 1))
    return {
        "parallel": parallel_beam(12, 1, 16, VolumeGeometry(8, 8, 1)),
        "fan": fan_beam(12, 1, 16, VolumeGeometry(8, 8, 1), sod=50.0,
                        sdd=100.0),
        "cone": cone_beam(6, 4, 16, vol, sod=50.0, sdd=100.0),
        "modular": modular_beam(src, -src, eu, ev, n_rows=4, n_cols=16,
                                vol=vol),
        "helical": helical_beam(1.5, 4.0, 12, 4, 16, vol, sod=50.0,
                                sdd=100.0),
    }


# -- construction / validation ---------------------------------------------- #
def test_spec_validates_eagerly(geom):
    with pytest.raises(ValueError, match="model"):
        ProjectorSpec(geom, model="nope")
    with pytest.raises(ValueError, match="backend"):
        ProjectorSpec(geom, backend="gpu")
    with pytest.raises(ValueError, match="mode"):
        ProjectorSpec(geom, mode="lazy")
    with pytest.raises(ValueError):
        ProjectorSpec(geom, compute_dtype="float16")
    with pytest.raises(TypeError, match="KernelConfig"):
        ProjectorSpec(geom, config={"bu": 8})
    with pytest.raises(TypeError, match="CTGeometry"):
        ProjectorSpec("not a geometry")


def test_spec_canonicalizes_dtype_aliases(geom):
    assert ProjectorSpec(geom, compute_dtype="bf16").compute_dtype == "bfloat16"
    assert (ProjectorSpec(geom, compute_dtype="bf16")
            == ProjectorSpec(geom, compute_dtype="bfloat16"))


# -- content identity -------------------------------------------------------- #
def test_spec_equality_is_content_based():
    vol = VolumeGeometry(8, 8, 1)
    a = ProjectorSpec(parallel_beam(12, 1, 16, vol))
    b = ProjectorSpec(parallel_beam(12, 1, 16, VolumeGeometry(8, 8, 1)))
    assert a == b and hash(a) == hash(b)
    assert a != ProjectorSpec(parallel_beam(12, 1, 16, vol), model="joseph")
    assert a.bucket_key() == b.bucket_key()
    assert a.replace(mode="exact") != a


def test_spec_hashable_in_sets(geom):
    s = {ProjectorSpec(geom), ProjectorSpec(geom),
         ProjectorSpec(geom, model="joseph")}
    assert len(s) == 2


def test_config_participates_in_identity(geom):
    pinned = ProjectorSpec(geom, config=KernelConfig(bu=8))
    assert pinned != ProjectorSpec(geom)
    assert pinned.bucket_key() != ProjectorSpec(geom).bucket_key()


# -- stable geometry hashing (the bugfix) ------------------------------------ #
def test_geometry_hash_float_repr_stable():
    vol = VolumeGeometry(8, 8, 1)
    a = fan_beam(12, 1, 16, vol, sod=50.0, sdd=100.0)
    b = fan_beam(12, 1, 16, vol, sod=np.float32(50.0), sdd=np.float64(100.0))
    assert a.canonical_hash() == b.canonical_hash()
    assert a.key() == b.key()


@pytest.mark.parametrize("kind", ["parallel", "fan", "cone", "modular",
                                  "helical"])
def test_to_config_roundtrip_hash(kind):
    g = _geoms()[kind]
    g2 = from_config(g.to_config())
    assert g2.canonical_hash() == g.canonical_hash()
    assert ProjectorSpec(g) == ProjectorSpec(g2)


def test_modular_frames_hashed_by_content():
    g = _geoms()["modular"]
    cfg = g.to_config()
    g2 = from_config(cfg)
    assert g2.canonical_hash() == g.canonical_hash()
    cfg_moved = dict(cfg)
    src = np.asarray(cfg["source_pos"], float).copy()
    src[0, 0] += 1.0
    cfg_moved["source_pos"] = src.tolist()
    assert from_config(cfg_moved).canonical_hash() != g.canonical_hash()


# -- op-cache key unification ------------------------------------------------ #
def test_equal_specs_share_cached_bundle(geom):
    ops.clear_cache()
    f = jnp.ones(geom.vol.shape)
    a = ops.forward_project(f, ProjectorSpec(geom))
    size1 = ops.cache_stats()["size"]
    b = ops.forward_project(f, ProjectorSpec(
        parallel_beam(12, 1, 16, VolumeGeometry(8, 8, 1))))
    assert ops.cache_stats()["size"] == size1
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0)


# -- the one refusal -------------------------------------------------------- #
_ENTRY_POINTS = {
    "Projector": lambda g: Projector(g),
    "get_ops": lambda g: ops.get_ops(g),
    "forward_project": lambda g: ops.forward_project(jnp.ones(g.vol.shape), g),
    "back_project": lambda g: ops.back_project(jnp.ones(g.sino_shape), g),
    "resolve_mode": lambda g: ops.resolve_mode(g),
    "resolve_backend": lambda g: ops.resolve_backend(g),
}


@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_spec_plus_kwargs_is_an_error(geom, entry):
    """Every entry point takes a ProjectorSpec and nothing else: a bare
    geometry raises one TypeError that names ProjectorSpec."""
    with pytest.raises(TypeError, match=f"{entry} takes a ProjectorSpec"):
        _ENTRY_POINTS[entry](geom)


def test_projector_backcompat_attributes(geom):
    proj = Projector(ProjectorSpec(geom, compute_dtype="bf16"))
    assert proj.geom is geom
    assert proj.model == "sf" and proj.backend == "auto"
    assert proj.mode == "auto" and proj.compute_dtype == "bfloat16"
    assert proj.config is None


# -- ShardSpec ---------------------------------------------------------------- #
def test_shard_spec_validation():
    with pytest.raises(ValueError, match="mesh_axes"):
        ShardSpec(mesh_axes=("data",))
    with pytest.raises(ValueError, match="angle axis"):
        ShardSpec(mesh_axes=(None, "model"))
    with pytest.raises(ValueError, match="distinct"):
        ShardSpec(mesh_axes=("data", "data"))
    with pytest.raises(ValueError, match=">= 1"):
        ShardSpec(angle_shards=0)
    with pytest.raises(ValueError, match="z mesh axis"):
        ShardSpec(mesh_axes=("data", None), z_shards=2)
    with pytest.raises(ValueError, match="halo"):
        ShardSpec(halo=-1)
    with pytest.raises(ValueError, match="meaningless"):
        ShardSpec(z_shards=1, halo=2)
    with pytest.raises(ValueError, match="comm_blocks"):
        ShardSpec(comm_blocks=-1)


def test_shard_spec_hash_roundtrip():
    a = ShardSpec(("data", "model"), angle_shards=4, z_shards=2, halo=3)
    b = ShardSpec(("data", "model"), angle_shards=4, z_shards=2, halo=3)
    assert a == b and hash(a) == hash(b)
    assert a.replace(halo=2) != a
    assert a.angle_axis == "data" and a.z_axis == "model"
    # round-trips through its own field dict (config-file currency)
    import dataclasses
    c = ShardSpec(**dataclasses.asdict(a))
    assert c == a and hash(c) == hash(a)
    assert len({a, b, a.replace(comm_blocks=1)}) == 2


def test_shard_participates_in_spec_identity(geom):
    shard = ShardSpec(("data", "model"), angle_shards=2, z_shards=2, halo=1)
    plain = ProjectorSpec(geom)
    sharded = ProjectorSpec(geom, shard=shard)
    assert plain != sharded and hash(plain) != hash(sharded)
    assert plain.bucket_key() != sharded.bucket_key()
    assert plain.cache_key() != sharded.cache_key()
    # same layout content -> same identity, regardless of object
    again = ProjectorSpec(geom, shard=ShardSpec(("data", "model"),
                                                angle_shards=2, z_shards=2,
                                                halo=1))
    assert sharded == again and hash(sharded) == hash(again)
    assert sharded.bucket_key() == again.bucket_key()
    # different layouts must not share serving buckets or cache entries
    other = ProjectorSpec(geom, shard=shard.replace(comm_blocks=1))
    assert other != sharded and other.bucket_key() != sharded.bucket_key()
    with pytest.raises(TypeError, match="ShardSpec"):
        ProjectorSpec(geom, shard="angle")
    assert "shard=" in repr(sharded)
