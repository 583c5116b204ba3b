"""End-to-end training driver.

Production machinery on any scale: pjit-sharded train step, deterministic
sharded data pipeline, atomic async checkpointing with auto-resume, gradient
clipping, (optional) 1-bit error-feedback gradient compression for the DP
axis, and supervisor-based crash restart.

CT training (supervised + DC losses, EMA, checkpoint/resume, eval harness,
data-parallel under ``data_parallel=True``) is :mod:`repro.launch.ct_train`.

Examples:
    # smoke-train an assigned arch (reduced config) on CPU
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 30 --batch 8 --seq 128 --ckpt-dir /tmp/ck

    # resume is automatic: re-running picks up from the latest checkpoint
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.tokens import TokenPipeline
from repro.launch import sharding
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import model as MD
from repro.optim import adamw, warmup_cosine
from repro.runtime import checkpoint as CKPT
from repro.runtime import compression
from repro.runtime.fault import Supervisor


def build(cfg, mesh, lr=3e-4, total_steps=10_000, compress=False):
    opt = adamw(warmup_cosine(lr, min(100, total_steps // 10 + 1), total_steps),
                weight_decay=0.1)
    ac = sharding.make_ac(mesh, cfg)
    comp_state = {"res": None}

    compress_fn = None
    if compress:
        def compress_fn(grads):
            q, comp_state["res"] = compression.compress(grads, comp_state["res"])
            return q

    step_fn = make_train_step(cfg, opt, ac, compress_fn=compress_fn)
    return opt, step_fn, ac


def train_loop(cfg, mesh, pipeline, steps: int, ckpt_dir: str = None,
               ckpt_every: int = 20, log_every: int = 5, seed: int = 0,
               fail_at_step: int = None):
    opt, step_fn, ac = build(cfg, mesh)
    aparams = MD.abstract_params(cfg)
    pshard = sharding.param_shardings(cfg, aparams, mesh)
    with mesh:
        params = jax.jit(lambda k: MD.init_params(cfg, k),
                         out_shardings=pshard)(jax.random.PRNGKey(seed))
        opt_state = jax.jit(opt.init, out_shardings=None)(params)

    start = 0
    ckpt = CKPT.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    if ckpt_dir and CKPT.latest_step(ckpt_dir) is not None:
        (params, opt_state), extra, start = CKPT.restore(
            ckpt_dir, (jax.device_get(params), jax.device_get(opt_state)))
        pipeline.load_state_dict(extra["data"])
        with mesh:
            params = jax.device_put(params, pshard)
            opt_state = jax.device_put(opt_state)
        print(f"[restore] resumed from step {start}")

    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    losses = []
    t0 = time.time()
    for i in range(start, steps):
        # Drive the pipeline by explicit step index: the prefetch iterator
        # may run ahead of the train step, so checkpointing its internal
        # counter would replay the wrong batch on resume (found by
        # tests/test_launch.py::test_train_loop_checkpoint_resume).
        toks = pipeline.batch(i)
        pipeline.step = i + 1
        if fail_at_step is not None and i == fail_at_step:
            raise RuntimeError("injected failure (fault-tolerance test)")
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (toks.shape[0], cfg.vision_tokens, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        with mesh:
            params, opt_state, metrics = jstep(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if i % log_every == 0:
            print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                  f"({(time.time()-t0)/max(i-start+1,1):.2f}s/step)")
        if ckpt and (i + 1) % ckpt_every == 0:
            ckpt.save(i + 1, (params, opt_state),
                      {"data": pipeline.state_dict()})
    if ckpt:
        ckpt.save(steps, (params, opt_state), {"data": pipeline.state_dict()})
        ckpt.wait()
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_local_mesh())
    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch)
    if cfg.n_codebooks > 1:
        base = pipe.batch
        pipe.batch = lambda step=None: np.stack(
            [base(step)] * cfg.n_codebooks, axis=1)

    attempts = {"n": 0}

    def loop(start):
        attempts["n"] += 1
        # inject the failure only on the first attempt (simulated node loss)
        fail = args.fail_at if attempts["n"] == 1 else None
        train_loop(cfg, mesh, pipe, args.steps, args.ckpt_dir,
                   ckpt_every=args.ckpt_every, fail_at_step=fail)
        return args.steps

    def restore():
        if args.ckpt_dir:
            return CKPT.latest_step(args.ckpt_dir) or 0
        return 0

    Supervisor(loop, restore, max_restarts=args.max_restarts).run()
    print("done.")


if __name__ == "__main__":
    main()
