"""Pallas TPU kernel: single-token GQA decode attention (flash-decoding).

The decode hot loop is pure HBM streaming: the KV cache (GBs) is read once
per token while compute is tiny, so the kernel's job is to keep the read
perfectly sequential and fuse the online softmax so nothing round-trips.

Grid = (B, KVH, Sk/BK), key axis innermost/'arbitrary'; scratch carries the
online-softmax state for the G = H/KVH query heads that share each KV head.
Valid-length masking handles both ragged fills and rolling-window buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 512
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bk: int, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bi = pl.program_id(0)
    valid_len = len_ref[bi]
    k_start = ki * bk

    @pl.when(k_start < valid_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, Dh]
        k = k_ref[0, 0].astype(jnp.float32)            # [BK, Dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [G, BK]
        s = s * (1.0 / (q.shape[-1] ** 0.5))
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < valid_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_gqa_grouped(q, k, v, lengths, *, bk=DEFAULT_BK, interpret=False):
    """q: [B, KVH, G, Dh]; k/v: [B, KVH, Sk, Dh]; lengths: [B] int32.
    Returns [B, KVH, G, Dh] f32. Sk % bk == 0 (ops pads)."""
    b, kvh, g, dh = q.shape
    sk = k.shape[2]
    nk = sk // bk
    grid = (b, kvh, nk)
    kernel = functools.partial(_kernel, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, dh), lambda b_, h_, ki, *_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, ki, *_: (b_, h_, ki, 0)),
                pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, ki, *_: (b_, h_, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, dh),
                                   lambda b_, h_, ki, *_: (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, q, k, v)
