"""Shared transformer layers: norms, RoPE, GQA attention, MLP.

Pure functions over parameter dicts built from spec.P descriptors. All
attention paths support GQA (n_kv_heads <= n_heads), optional qk-norm
(qwen3/chameleon), optional sliding windows (hymba), causal or bidirectional
masks, and a KV-cache decode mode. The prefill attention dispatches to the
Pallas flash kernel when enabled (kernels.flash_attention), otherwise to the
pure-jnp reference path (identical math; the kernel is validated against it).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .spec import P

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_params(d: int) -> dict:
    return {"scale": P((d,), ("embed",), init="ones")}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """Rotary embedding. x: [B, S, H, Dh]; positions: [B, S] or [S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?, S, half]
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    causal: bool = True
    window: int = 0          # 0 = full attention; >0 = sliding window
    rope_theta: float = 1e4
    use_rope: bool = True
    chunk: int = 0           # >0: chunked (flash-style) attention, O(S*chunk)
                             # logits memory instead of O(S^2)


def attention_params(cfg: AttnConfig) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": P((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": P((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": P((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": P((dh,), (None,), init="ones")}
        p["k_norm"] = {"scale": P((dh,), (None,), init="ones")}
    return p


def _qkv(params, cfg: AttnConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, cfg: AttnConfig, q_offset: int | jax.Array = 0):
    """Reference scaled-dot-product attention with GQA + masks.

    q: [B, Sq, H, Dh]; k/v: [B, Sk, KVH, Dh]. q_offset: absolute position of
    q[0] (for decode/cache). Returns [B, Sq, H, Dh]. f32 accumulation.
    """
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    qg = q.reshape(b, sq, kvh, groups, dh)
    # native-dtype dots with f32 accumulation: avoids materializing f32
    # copies of K/V (2-3x HBM traffic on the decode path — §Perf iter 5)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(dh).astype(jnp.float32)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if cfg.causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if cfg.window > 0:
        mask &= kpos[None, :] > qpos[:, None] - cfg.window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def _sdpa_chunked(q, k, v, cfg: AttnConfig):
    """Flash-style attention in pure XLA: scan over query blocks, full K per
    block, masked softmax in f32. Peak logits memory O(chunk * Sk) instead of
    O(Sq * Sk) — the memory-roofline fix for 32k prefill (§Perf). The Pallas
    kernel is the TPU-native equivalent; this path compiles everywhere and is
    what the dry-run lowers."""
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    c = min(cfg.chunk, sq)
    if sq % c != 0:
        return _sdpa(q, k, v, cfg)
    nq = sq // c
    groups = h // kvh
    qb = q.reshape(b, nq, c, h, dh).swapaxes(0, 1)  # [nq, B, c, H, Dh]
    kpos = jnp.arange(sk)

    def block(_, xs):
        qi, qblk = xs
        qg = qblk.reshape(b, c, kvh, groups, dh)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(dh)
        qpos = qi * c + jnp.arange(c)
        mask = jnp.ones((c, sk), bool)
        if cfg.causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if cfg.window > 0:
            mask &= kpos[None, :] > qpos[:, None] - cfg.window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return None, out.reshape(b, c, h, dh).astype(q.dtype)

    _, blocks = jax.lax.scan(block, None, (jnp.arange(nq), qb))
    return blocks.swapaxes(0, 1).reshape(b, sq, h, dh)


def attention(params, cfg: AttnConfig, x, positions=None, *,
              kv: Optional[tuple] = None, use_kernel: bool = False):
    """Full-sequence attention (train/prefill). x: [B, S, D].

    kv: optional external (k, v) for cross-attention (whisper decoder).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _qkv(params, cfg, x, positions)
    if kv is not None:
        k, v = kv
    if use_kernel and kv is None:
        from ..kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=cfg.causal,
                                     window=cfg.window)
    elif cfg.chunk > 0:
        out = _sdpa_chunked(q, k, v, cfg)
    else:
        out = _sdpa(q, k, v, cfg)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


class KVCache(NamedTuple):
    k: jax.Array   # [B, S_max, KVH, Dh]
    v: jax.Array
    length: jax.Array  # scalar int32 — tokens currently cached


def init_kv_cache(batch: int, max_seq: int, cfg: AttnConfig,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def _cache_update(cache_arr, new, slot, mesh):
    """Write one token's K/V at a dynamic slot.

    With the cache sequence dim sharded over `model`, a plain
    dynamic_update_slice makes GSPMD rewrite the op as full-cache f32 selects
    plus an all-gather (~10x the physical decode traffic — §Perf iter 6).
    shard_map makes the write local to the owning rank: O(one token) traffic.
    """
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    if (mesh is None or "model" not in mesh.axis_names
            or dict(zip(mesh.axis_names,
                        mesh.devices.shape)).get("model", 1) <= 1
            or cache_arr.shape[1] % mesh.shape["model"] != 0):
        return jax.lax.dynamic_update_slice(
            cache_arr, new.astype(cache_arr.dtype), (zero, slot, zero, zero))

    from jax.sharding import PartitionSpec
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_spec = dp if len(dp) != 1 else dp[0]

    def inner(c, n, s):
        s_loc = c.shape[1]
        rank = jax.lax.axis_index("model").astype(jnp.int32)
        ls = s - rank * s_loc
        inb = (ls >= 0) & (ls < s_loc)
        ls_c = jnp.clip(ls, 0, s_loc - 1)
        z = jnp.zeros((), jnp.int32)
        old = jax.lax.dynamic_slice(
            c, (z, ls_c, z, z), (c.shape[0], 1, c.shape[2], c.shape[3]))
        upd = jnp.where(inb, n.astype(c.dtype), old)
        return jax.lax.dynamic_update_slice(c, upd, (z, ls_c, z, z))

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(PartitionSpec(dp_spec, "model", None, None),
                  PartitionSpec(dp_spec, None, None, None),
                  PartitionSpec()),
        out_specs=PartitionSpec(dp_spec, "model", None, None),
        check_vma=False,
    )(cache_arr, new, slot)


def attention_decode(params, cfg: AttnConfig, x, cache: KVCache, *,
                     use_kernel: bool = False, mesh=None):
    """Single-token decode. x: [B, 1, D]; returns (out [B,1,D], new cache).

    With a sliding window the cache is a rolling buffer of size window.
    """
    b = x.shape[0]
    pos = cache.length
    q, k_new, v_new = _qkv(params, cfg, x, jnp.full((b, 1), pos))
    size = cache.k.shape[1]
    slot = jnp.where(cfg.window > 0, pos % size, pos)
    k = _cache_update(cache.k, k_new, slot, mesh)
    v = _cache_update(cache.v, v_new, slot, mesh)
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    groups = cfg.n_heads // kvh
    qg = q.reshape(b, kvh, groups, dh)
    if use_kernel:
        from ..kernels.decode_gqa import ops as dg_ops
        valid_len = jnp.minimum(pos + 1, size)
        out = dg_ops.decode_gqa(q[:, 0], k, v, valid_len)
    else:
        logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(dh)
        kpos = jnp.arange(size)
        valid = kpos <= pos if cfg.window == 0 else (
            (kpos <= pos) | (pos >= size)
        )
        logits = jnp.where(valid[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out.reshape(b, cfg.n_heads, dh)
    out = out.reshape(b, 1, cfg.n_heads, dh).astype(x.dtype)
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return proj, KVCache(k=k, v=v, length=pos + 1)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_params(d: int, f: int, gated: bool = True) -> dict:
    p = {
        "w_in": P((d, f), ("embed", "mlp")),
        "w_out": P((f, d), ("mlp", "embed")),
    }
    if gated:
        p["w_gate"] = P((d, f), ("embed", "mlp"))
    return p


def mlp(params: dict, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"].astype(x.dtype))
    if "w_gate" in params:
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"].astype(x.dtype))
