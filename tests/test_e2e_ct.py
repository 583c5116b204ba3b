"""End-to-end reproduction of the paper's §4 experiment (reduced scale):
limited-angle CT -> U-Net prediction -> sinogram completion + iterative
data-consistency refinement must improve PSNR over the raw prediction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Projector, ProjectorSpec, VolumeGeometry, parallel_beam
from repro.data.pipeline import CTDataPipeline
from repro.nn.unet import unet_apply, unet_init
from repro.optim import adamw, apply_updates, constant
from repro.recon import complete_and_refine


def psnr(a, b, peak):
    mse = float(jnp.mean((a - b) ** 2))
    return 10 * np.log10(peak ** 2 / max(mse, 1e-20))


@pytest.fixture(scope="module")
def trained():
    vol = VolumeGeometry(32, 32, 1)
    geom = parallel_beam(48, 1, 48, vol)
    proj = Projector(ProjectorSpec(geom, "sf"))
    pipe = CTDataPipeline(geom, batch_size=4, seed=0, mode="limited_angle",
                          available_deg=60.0)
    params = unet_init(jax.random.PRNGKey(0), base=8, levels=2)
    opt = adamw(constant(1e-3))
    state = opt.init(params)

    def loss_fn(p, x_in, x_gt, sino, mask):
        pred = unet_apply(p, x_in[..., None])[..., 0]
        rec_loss = jnp.mean((pred - x_gt) ** 2)
        # the paper's data-consistency term through the differentiable A
        dc = jnp.mean(jnp.square((proj(pred[..., None]) - sino) * mask))
        return rec_loss + 0.1 * dc

    step = jax.jit(lambda p, s, a, b, c, d: _step(p, s, a, b, c, d))

    def _step(p, s, x_in, x_gt, sino, mask):
        l, g = jax.value_and_grad(loss_fn)(p, x_in, x_gt, sino, mask)
        u, s = opt.update(g, s, p)
        return apply_updates(p, u), s, l

    data = []
    for i in range(4):
        imgs, masks = pipe.batch(i)
        gt = jnp.asarray(imgs)
        sino = proj(gt[..., None])
        mvec = jnp.asarray(masks)[:, :, None, None]
        x_in = proj.fbp(sino * mvec)[..., 0]
        data.append((x_in, gt, sino, mvec))
    losses = []
    for i in range(80):
        a, b, c, d = data[i % 4]
        params, state, l = step(params, state, a, b, c, d)
        losses.append(float(l))
    return proj, pipe, params, losses


def test_training_converges(trained):
    _, _, _, losses = trained
    assert np.mean(losses[-8:]) < 0.6 * np.mean(losses[:4]), losses[::16]


def test_data_consistency_refinement_improves_psnr(trained):
    proj, pipe, params, _ = trained
    # held-out sample
    img, mask = pipe.sample(10_000, 0)
    gt = jnp.asarray(img)
    sino = proj(gt[..., None])
    mvec = jnp.asarray(mask)[:, None, None]
    x_in = proj.fbp(sino * mvec)[..., 0]
    pred = unet_apply(params, x_in[None, ..., None])[0, ..., 0]
    peak = float(gt.max())
    p_fbp = psnr(x_in, gt, peak)
    p_net = psnr(pred, gt, peak)
    x_ref, completed = complete_and_refine(proj, pred[..., None], sino, mvec,
                                           n_iters=20, beta=0.05)
    p_ref = psnr(x_ref[..., 0], gt, peak)
    # net beats raw limited-angle FBP; refinement beats the net (paper Fig. 3)
    assert p_net > p_fbp, (p_fbp, p_net)
    assert p_ref > p_net, (p_net, p_ref)
    # measured views preserved exactly in the completed sinogram
    keep = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(completed)[keep],
                               np.asarray(sino)[keep], rtol=0, atol=0)


def test_gradients_flow_through_projector(trained):
    proj, pipe, params, _ = trained
    img, mask = pipe.sample(11_000, 0)
    gt = jnp.asarray(img)
    sino = proj(gt[..., None])
    mvec = jnp.asarray(mask)[:, None, None]
    x_in = proj.fbp(sino * mvec)[..., 0]

    def dc_loss(p):
        pred = unet_apply(p, x_in[None, ..., None])[0, ..., 0]
        return jnp.mean(jnp.square((proj(pred[..., None]) - sino) * mvec))

    g = jax.grad(dc_loss)(params)
    total = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(total) and total > 0
