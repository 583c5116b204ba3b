"""System-level behaviour: the public API composes end to end (the paper's
Listing-1 usage pattern), batching, jit caching, and config-file driving."""
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Projector, ProjectorSpec, VolumeGeometry,
                        back_project, fbp, forward_project, from_config,
                        parallel_beam)


def test_listing1_usage_pattern():
    """The paper's PyTorch snippet, in JAX: projector inside a model."""
    vol = VolumeGeometry(24, 24, 1)
    geom = parallel_beam(12, 1, 36, vol)
    proj = Projector(ProjectorSpec(geom))

    def model(theta, measured):
        # trivial 'network': volume is the parameter; loss is Ax - y
        return jnp.mean(jnp.square(proj(theta) - measured))

    theta = jnp.zeros(vol.shape)
    y = jnp.ones(geom.sino_shape)
    g = jax.grad(model)(theta, y)
    assert g.shape == vol.shape
    assert float(jnp.abs(g).sum()) > 0


def test_batched_projection():
    vol = VolumeGeometry(16, 16, 2)
    geom = parallel_beam(6, 2, 24, vol)
    f = jax.random.normal(jax.random.PRNGKey(0), (3,) + vol.shape)
    sino = forward_project(f, ProjectorSpec(geom))
    assert sino.shape == (3,) + geom.sino_shape
    one = forward_project(f[1], ProjectorSpec(geom))
    np.testing.assert_allclose(np.asarray(sino[1]), np.asarray(one),
                               rtol=1e-5, atol=1e-6)
    vols = back_project(sino, ProjectorSpec(geom))
    assert vols.shape == f.shape


def test_op_cache_reuse():
    from repro.kernels.ops import get_ops
    vol = VolumeGeometry(16, 16, 2)
    geom = parallel_beam(6, 2, 24, vol)
    fp1, bp1 = get_ops(ProjectorSpec(geom, "sf", "ref"))
    fp2, bp2 = get_ops(ProjectorSpec(geom, "sf", "ref"))
    assert fp1 is fp2 and bp1 is bp2   # lru-cached per geometry key


def test_config_file_driving(tmp_path):
    cfg = {"geom_type": "parallel", "n_angles": 8, "n_rows": 2, "n_cols": 24,
           "volume": {"nx": 16, "ny": 16, "nz": 2}}
    p = tmp_path / "geom.json"
    p.write_text(json.dumps(cfg))
    geom = from_config(json.loads(p.read_text()))
    f = jnp.ones(geom.vol.shape)
    rec = fbp(forward_project(f, ProjectorSpec(geom)), geom)
    assert rec.shape == geom.vol.shape


def test_jit_compatible_end_to_end():
    vol = VolumeGeometry(16, 16, 1)
    geom = parallel_beam(8, 1, 24, vol)
    proj = Projector(ProjectorSpec(geom))

    @jax.jit
    def recon_loss(x, y):
        return 0.5 * jnp.sum((proj(x) - y) ** 2)

    x = jnp.ones(vol.shape)
    y = proj(x)
    assert float(recon_loss(x, y)) < 1e-6
