"""Pallas TPU flash attention (prefill/training forward): tiled online
softmax, GQA, causal + sliding-window masks.

Grid = (B, H, Sq/BQ, Sk/BK) with the key axis innermost and 'arbitrary'
semantics (sequential per core) so the (m, l, acc) running state lives in
VMEM scratch across key blocks. Q blocks are [BQ, Dh] tiles against K/V
[BK, Dh] tiles: the two dots per block hit the MXU at 128-aligned shapes;
masks and the online-softmax rescale run on the VPU in f32.

Memory: per program instance VMEM = BQ*Dh (q) + 2*BK*Dh (k,v) + BQ*BK (s)
+ BQ*Dh (acc) floats ~= 0.6 MB at BQ=BK=256, Dh=128 — well inside the
~16 MB/core budget, leaving room for double buffering of the K/V stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, window: int, bq: int, bk: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # skip fully-masked blocks (causal: keys after the last query; window:
    # keys before the reachable horizon)
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window > 0:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [BQ, Dh]
        k = k_ref[0, 0].astype(jnp.float32)           # [BK, Dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [BQ, BK]
        s = s * (1.0 / (q.shape[-1] ** 0.5))
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0,
                         bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=False):
    """q: [B, H, Sq, Dh]; k/v: [B, KVH, Sk, Dh] -> [B, H, Sq, Dh].

    Sq % bq == 0 and Sk % bk == 0 (ops.py pads); H % KVH == 0 (GQA).
    """
    b, h, sq, dh = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    nq, nk = sq // bq, sk // bk
    grid = (b, h, nq, nk)
    kernel = functools.partial(_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, qi, ki, g=g: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, qi, ki, g=g: (b_, h_ // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
