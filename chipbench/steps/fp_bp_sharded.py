"""Step kind ``fp_bp_sharded``: the ``fp_bp`` step of a configuration that
one chip cannot hold, laid out on the cell's chips as the configuration's
``shard`` block states, through the program's normal sharded path
``ProjectorSpec(geom, ..., shard=ShardSpec(...))`` ->
``DistributedProjector(spec, mesh)``:

    r = A x - b,   g = A^T r

over every view, as one jitted program.  ``x`` and ``b`` are made in place
in the z-slab layout from the seed, each chip drawing its own slab: whole
arrays on one device would not fit.

The check runs :func:`chipbench.reference.sf_fp_bp` on the configuration
of one slab (``nz`` and ``n_rows`` of one slab, every other number the
configuration's), on each chip for the slab it holds, all slabs at once.
That is the whole reference for a parallel beam whose slabs pair slices
and detector rows of the same height: rays stay in their z-plane, so slab
``k`` of the volume and row block ``k`` of the sinogram see the same
geometry as the centred slab.  The step refuses a configuration for which
that does not hold.  It compares ``r`` whole and ``g`` on ``bp_rows`` x
rows drawn from the seed (all rows where the check file sets none).

``work()`` is one slab's share, the work of each chip: the roofline
readers compare it with one chip's peak against device time averaged over
the chips.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import inputs, reference, work
from chipbench.steps import fp_bp
from chipbench.steps.fp_bp import _rel

SCOPES = fp_bp.SCOPES


def slab_config(cfg: dict) -> dict:
    """The configuration of one z-slab: the volume's ``nz`` and the
    detector's ``n_rows`` over the z shards, every other number as given.
    Raises where the slabs are not independent copies of it."""
    g, sh = cfg["geometry"], cfg["shard"]
    vol, z = g["volume"], sh["z_shards"]
    if g["geom_type"] != "parallel" or sh["halo"] != 0:
        raise ValueError("fp_bp_sharded checks parallel-beam z-slabs with "
                         "halo 0 only")
    if (vol["nz"] % z or g["n_rows"] % z or vol["nz"] != g["n_rows"]
            or vol.get("dz", 1.0) != g.get("pixel_height", 1.0)
            or vol.get("offset_z", 0.0) != g.get("center_row", 0.0)):
        raise ValueError("slab k must pair slices and detector rows of the "
                         "same height and centre")
    slab = dict(g, volume=dict(vol, nz=vol["nz"] // z),
                n_rows=g["n_rows"] // z)
    if (slab["volume"]["nz"], slab["n_rows"]) != (sh["slab_slices"],
                                                 sh["slab_rows"]):
        raise ValueError(f"z_shards={z} does not give the stated slab of "
                         f"{sh['slab_slices']} slices, {sh['slab_rows']} rows")
    return dict(cfg, geometry=slab)


class Step(fp_bp.Step):
    """One cell's program and inputs on its mesh; running, releasing and
    the row sample as ``fp_bp``'s."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, sample: dict,
                 compute_dtype=None, precision=None):
        super().__init__(cfg, traffic, seed, sample, compute_dtype, precision)
        self.slab = slab_config(cfg)
        self.axes = tuple(cfg["shard"]["axes"])
        self.vol_spec = P(None, None, self.axes[1])
        self.sino_spec = P(self.axes[0], self.axes[1], None)

    def setup(self):
        from repro import compat
        from repro.core.distributed import DistributedProjector
        from repro.core.geometry import from_config
        from repro.core.spec import ProjectorSpec, ShardSpec
        from repro.kernels import ops
        sh, p = self.cfg["shard"], self.cfg["projector"]
        devs = jax.devices()[:sh["chips"]]
        if len(devs) < sh["chips"]:
            raise RuntimeError(f"the layout needs {sh['chips']} devices, "
                               f"JAX found {len(devs)}")
        self.mesh = compat.make_mesh(tuple(sh["mesh"]), self.axes,
                                     devices=devs)
        shard = ShardSpec(self.axes, angle_shards=sh["angle_shards"],
                          z_shards=sh["z_shards"], halo=sh["halo"])
        spec = ProjectorSpec(from_config(self.cfg["geometry"]),
                             model=p["model"], backend=p["backend"],
                             mode=p["mode"],
                             compute_dtype=self.compute_dtype, shard=shard)
        dp = DistributedProjector(spec, self.mesh)
        self.x, self.b = self._inputs()

        def step(x, b):
            with jax.named_scope("fp"):
                s = dp(x)
            with jax.named_scope("residual"):
                r = s - b
            with jax.named_scope("bp"):
                g = dp.T(r)
            return r, g

        with jax.default_matmul_precision(self.precision):
            self.compiled = jax.jit(step).lower(self.x, self.b).compile()
        local = spec.replace(shard=None)
        # comm_blocks: None from a program whose pair does not report it.
        self.notes = {"backend": ops.resolve_backend(local),
                      "mode": ops.resolve_mode(local),
                      "devices": len(devs), "z_shards": shard.z_shards,
                      "halo": shard.halo,
                      "comm_blocks": getattr(dp, "comm_blocks", None)}

    def _inputs(self):
        """``inputs.uniform_pair``'s arrays, each chip drawing its slab."""
        g = reference.Geom.from_config(self.cfg["geometry"])
        vol_shape = (g.nx, g.ny, g.nz)
        sino_shape = (len(g.angles), g.n_rows, g.n_cols)
        v, m = self.traffic["volume"], self.traffic["measured"]
        out = (NamedSharding(self.mesh, self.vol_spec),
               NamedSharding(self.mesh, self.sino_spec))

        @partial(jax.jit, out_shardings=out)
        def make(k):
            kx, kb = jax.random.split(k)
            x = jax.random.uniform(kx, vol_shape, jnp.float32, v["low"],
                                   v["high"])
            b = jax.random.uniform(kb, sino_shape, jnp.float32, m["low"],
                                   m["high"])
            return x, b

        return jax.block_until_ready(make(inputs.key(self.seed)))

    def work(self) -> dict:
        return work.sf_call(self.slab)

    def reference(self, rows):
        """``(r, g)`` of the reference, each chip computing its own slab's
        (``g`` on ``rows``), in the inputs' layout."""
        geom = self.slab["geometry"]
        # The check imports nothing of the program, its compat shim included.
        per_slab = jax.shard_map(  # repro-lint: disable=RL003
            lambda x, b: reference.sf_fp_bp(geom, x, b, rows),
            mesh=self.mesh, in_specs=(self.vol_spec, self.sino_spec),
            out_specs=(self.sino_spec, self.vol_spec), check_vma=False)
        return jax.jit(per_slab)(self.x, self.b)

    def check(self, out) -> dict:
        r, g = out
        rows = self.bp_rows()
        r_ref, g_ref = self.reference(rows)
        if rows is not None:
            g = g[jnp.asarray(rows)]
        return {"err_r": _rel(r, r_ref), "err_g": _rel(g, g_ref)}
