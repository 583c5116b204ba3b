"""The general generator: a traffic file's parameters and ``--seed`` in,
device arrays out, each set made in one jitted call on the device.

Every seed gives inputs of the same shapes and the same amount of work;
the seed changes only values.  The seed may be any whole number that fits
in 64 bits (``jax.random.key`` takes it whole).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from chipbench.reference import Geom


def key(seed: int):
    return jax.random.key(int(seed) % 2 ** 64)


def uniform_pair(cfg_geom: dict, spec: dict, seed: int):
    """A volume and a measured sinogram, each uniform on the traffic's
    ``[low, high)``: non-negative like attenuation and line integrals."""
    g = Geom.from_config(cfg_geom)
    vol_shape = (g.nx, g.ny, g.nz)
    sino_shape = (len(g.angles), g.n_rows, g.n_cols)
    v, m = spec["volume"], spec["measured"]

    @jax.jit
    def make(k):
        kx, kb = jax.random.split(k)
        x = jax.random.uniform(kx, vol_shape, jnp.float32, v["low"], v["high"])
        b = jax.random.uniform(kb, sino_shape, jnp.float32, m["low"], m["high"])
        return x, b

    return jax.block_until_ready(make(key(seed)))


def ellipsoid_sinogram(cfg_geom: dict, spec: dict, seed: int):
    """Exact parallel-beam line integrals of ``spec["count"]`` ellipsoids of
    random centre, semi-axes, in-plane rotation and density (mm, 1/mm):
    the chord length of each ray through each ellipsoid, times its density,
    summed.  Sizes are shares of the volume's half width; every ellipsoid
    lies inside the volume's inscribed cylinder."""
    g = Geom.from_config(cfg_geom)
    if g.kind != "parallel":
        raise ValueError("ellipsoid_sinogram makes parallel-beam data")
    n = int(spec["count"])
    half = min(g.nx, g.ny) * g.dx / 2.0
    zhalf = g.nz * g.dz / 2.0
    lo, hi = spec["semi_axis_share"]
    dlo, dhi = spec["density"]
    us = jnp.asarray(g.us)[None, :]                          # (1, nu)
    vs = jnp.asarray(g.vs)[:, None]                          # (nv, 1)

    @jax.jit
    def make(k):
        ks = jax.random.split(k, 6)
        ax = jax.random.uniform(ks[0], (n, 3), jnp.float32, lo, hi) * \
            jnp.asarray([half, half, zhalf], jnp.float32)
        reach = half - jnp.maximum(ax[:, 0], ax[:, 1])
        rad = jax.random.uniform(ks[1], (n,)) * reach
        phi = jax.random.uniform(ks[2], (n,)) * 2 * np.pi
        cz = (jax.random.uniform(ks[3], (n,)) * 2 - 1) * (zhalf - ax[:, 2])
        psi = jax.random.uniform(ks[4], (n,)) * np.pi
        rho = jax.random.uniform(ks[5], (n,), jnp.float32, dlo, dhi)
        cx, cy = rad * jnp.cos(phi), rad * jnp.sin(phi)

        def view(ang):
            c, s = jnp.cos(ang), jnp.sin(ang)

            def one(acc, e):
                a, b, ez, ex, ey, ecz, ps, de = e
                # Ray points u (-s, c) + t (c, s) at height v, in the
                # ellipsoid's frame (rotated by -ps, scaled to a unit ball).
                px, py = -us * s - ex, us * c - ey                  # (1, nu)
                cp, sp = jnp.cos(ps), jnp.sin(ps)
                qx = (px * cp + py * sp) / a
                qy = (-px * sp + py * cp) / b
                dx_, dy_ = (c * cp + s * sp) / a, (-c * sp + s * cp) / b
                qz = (vs - ecz) / ez                                # (nv, 1)
                dd = dx_ * dx_ + dy_ * dy_
                qd = qx * dx_ + qy * dy_
                disc = qd * qd - dd * (qx * qx + qy * qy + qz * qz - 1.0)
                return acc + de * 2.0 * jnp.sqrt(jnp.maximum(disc, 0.0)) / dd, None

            out, _ = jax.lax.scan(
                one, jnp.zeros((g.n_rows, g.n_cols), jnp.float32),
                (ax[:, 0], ax[:, 1], ax[:, 2], cx, cy, cz, psi, rho))
            return out

        return jax.lax.map(view, jnp.asarray(g.angles))

    return jax.block_until_ready(make(key(seed)))
