"""Pallas TPU flash attention (forward): online-softmax with *causal block
skipping*.

Why it exists (EXPERIMENTS.md §Roofline): the pure-jnp chunked attention in
``models/layers.py`` must compute fully-masked off-diagonal blocks (XLA
cannot skip them), wasting ~2x attention FLOPs on causal training/prefill.
A Pallas grid can: blocks with ``kv_block > q_block`` are skipped with
``pl.when`` — no MXU work is issued for them.

Supports GQA (grid dimension per kv-head x group) and sliding windows
(blocks outside the window are skipped too).  Forward-only: the training
path keeps the jnp chunked implementation (autodiff-able); this kernel is
the serving/prefill fast path and the reference for a future custom-vjp
backward.

ref.py oracle: ``flash_ref`` below (numerically the standard softmax).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tune

NEG_INF = -1e30


def flash_ref(q, k, v, window: Optional[int] = None):
    """Oracle: q (B,H,S,hd), k/v (B,KV,S,hd) -> (B,H,S,hd), causal."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, S, hd)
    s = jnp.einsum("bkgsh,bkth->bkgst", qg, k).astype(jnp.float32)
    s = s / math.sqrt(hd)
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    keep = kp <= qp
    if window is not None:
        keep &= kp > qp - window
    s = jnp.where(keep[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,bkth->bkgsh", w, v)
    return o.reshape(B, H, S, hd)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
                  *, bq: int, bk: int, nk: int, scale: float,
                  window: Optional[int], hd: int):
    qi = pl.program_id(3)
    ki = pl.program_id(4)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q_start = qi * bq
    k_start = ki * bk
    needed = k_start <= q_start + bq - 1                 # causal block skip
    if window is not None:
        needed &= (k_start + bk - 1) > (q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, 0]                                # (bq, hd)
        k = k_ref[0, 0]                                   # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = kp <= qp
        if window is not None:
            keep &= kp > qp - window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_s[...]
        l_prev = l_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, window: Optional[int] = None,
                    bq: int = 512, bk: int = 512):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd).  Causal."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, S)
    if S % bq or S % bk:
        raise ValueError(
            f"flash_attention needs the sequence length to be divisible by "
            f"both block sizes, got S={S} with bq={bq}, bk={bk}; pad the "
            f"sequence or pass block sizes that divide it")
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, S, hd)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, window=window, hd=hd)
    grid = (B, KV, G, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd),
                         lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, kv, g, qi, ki: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, kv, g, qi, ki: (b, kv, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bq, hd),
                               lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=tune._interpret(),
    )(qg, k, v)
    return out.reshape(B, H, S, hd)


# --------------------------------------------------------------------------- #
# Backward (FlashAttention-2 style): two block-skipping kernels + custom_vjp
# --------------------------------------------------------------------------- #
def _flash_fwd_stats_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
                            *, bq, bk, nk, scale, window):
    qi = pl.program_id(3)
    ki = pl.program_id(4)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q_start, k_start = qi * bq, ki * bk
    needed = k_start <= q_start + bq - 1
    if window is not None:
        needed &= (k_start + bk - 1) > (q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = kp <= qp
        if window is not None:
            keep &= kp > qp - window
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m_s[...], s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_s[...] - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[0, 0, 0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                          ).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = (m_s[...] + jnp.log(
            jnp.maximum(l_s[...], 1e-30)))[:, 0]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc, *, bq, bk, nk, scale, window):
    qi = pl.program_id(3)
    ki = pl.program_id(4)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q_start, k_start = qi * bq, ki * bk
    needed = k_start <= q_start + bq - 1
    if window is not None:
        needed &= (k_start + bk - 1) > (q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = kp <= qp
        if window is not None:
            keep &= kp > qp - window
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc[...] += jax.lax.dot_general(ds, k.astype(jnp.float32),
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0, 0, 0] = acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc,
                          *, bq, bk, nq, scale, window):
    ki = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * bq, ki * bk
    needed = k_start <= q_start + bq - 1
    if window is not None:
        needed &= (k_start + bk - 1) > (q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        keep = kp <= qp
        if window is not None:
            keep &= kp > qp - window
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(ds, q.astype(jnp.float32),
                                           (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0, 0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _fwd_with_stats(q, k, v, window, bq, bk):
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, S, hd)
    kernel = functools.partial(_flash_fwd_stats_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, window=window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, KV, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd), lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, g, qi, ki: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, g, qi, ki: (b, kv, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd), lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kv, g, qi, ki: (b, kv, g, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, KV, G, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=tune._interpret(),
    )(qg, k, v)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_diff(q, k, v, window=None, bq=512, bk=512):
    """Differentiable flash attention (forward + FlashAttention-2 backward,
    both with causal block skipping).  Same signature as flash_attention."""
    B, H, S, hd = q.shape
    o, _ = _fwd_with_stats(q, k, v, window, min(bq, S), min(bk, S))
    return o.reshape(B, H, S, hd)


def _fa_fwd(q, k, v, window, bq, bk):
    B, H, S, hd = q.shape
    bq, bk = min(bq, S), min(bk, S)
    o, lse = _fwd_with_stats(q, k, v, window, bq, bk)
    return o.reshape(B, H, S, hd), (q, k, v, o, lse)


def _fa_bwd(window, bq, bk, res, do):
    q, k, v, o, lse = res
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    bq, bk = min(bq, S), min(bk, S)
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, S, hd)
    dog = do.reshape(B, KV, G, S, hd)
    delta = jnp.sum(dog.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, bq=bq, bk=bk, nk=nk,
                          scale=scale, window=window),
        grid=(B, KV, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd), lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, g, qi, ki: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, g, qi, ki: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, 1, bq, hd), lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kv, g, qi, ki: (b, kv, g, qi)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kv, g, qi, ki: (b, kv, g, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bq, hd),
                               lambda b, kv, g, qi, ki: (b, kv, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=tune._interpret(),
    )(qg, k, v, dog, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq,
                          scale=scale, window=window),
        grid=(B, KV, G, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd), lambda b, kv, g, ki, qi: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, g, ki, qi: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, g, ki, qi: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, 1, bq, hd), lambda b, kv, g, ki, qi: (b, kv, g, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kv, g, ki, qi: (b, kv, g, qi)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kv, g, ki, qi: (b, kv, g, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, bk, hd), lambda b, kv, g, ki, qi: (b, kv, g, ki, 0)),
            pl.BlockSpec((1, 1, 1, bk, hd), lambda b, kv, g, ki, qi: (b, kv, g, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, KV, G, S, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=tune._interpret(),
    )(qg, k, v, dog, lse, delta)
    # per-group dk/dv sum over the G query heads sharing each kv head
    dq = dq.reshape(B, H, S, hd)
    dk = dk.sum(axis=2)
    dv = dv.sum(axis=2)
    return dq, dk, dv


flash_attention_diff.defvjp(_fa_fwd, _fa_bwd)
