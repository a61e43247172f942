"""Mixture-of-Experts layer with sort-based token dispatch (expert parallel).

Top-k routing -> flatten (token, expert) assignments -> argsort by expert ->
capacity-bounded scatter into an [E, C, D] buffer -> batched per-expert
matmuls -> weighted scatter-add back to tokens. The [E, ...] dims carry the
"experts" logical axis, so experts shard over the `model` mesh axis (EP) and
GSPMD inserts the all-to-all at the token->expert boundary.

FLOP cost is top_k/E of the dense-all-experts equivalent (vs the E/top_k
overhead of naive one-hot dispatch), which is what makes the moonshot config
(64 experts, top-6) roofline-viable.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .spec import P


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int           # per-expert hidden size
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25


def moe_params(cfg: MoEConfig) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": P((d, e), ("embed", "experts"), scale=0.1),
        "w_gate": P((e, d, f), ("experts", "embed", "mlp")),
        "w_in": P((e, d, f), ("experts", "embed", "mlp")),
        "w_out": P((e, f, d), ("experts", "mlp", "embed")),
    }


class MoEOut(NamedTuple):
    y: jax.Array
    aux_loss: jax.Array    # load-balance loss (Switch-style)
    dropped_frac: jax.Array


def moe(params: dict, cfg: MoEConfig, x: jax.Array) -> MoEOut:
    """x: [B, S, D] -> MoEOut with y: [B, S, D]."""
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.n_experts
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf, params["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)               # [T, k]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # load-balance aux loss: E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                              # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32), axis=1),
        axis=0,
    ) / k
    aux = e * jnp.sum(me * ce)

    flat_expert = expert_idx.reshape(-1)                      # [T*k]
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = gate.reshape(-1)

    order = jnp.argsort(flat_expert)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]

    counts = jnp.bincount(flat_expert, length=e)
    starts = jnp.cumsum(counts) - counts                      # [E]
    pos = jnp.arange(t * k) - starts[sorted_expert]

    cap = max(1, int(round(t * k / e * cfg.capacity_factor)))
    keep = pos < cap
    buf_idx = jnp.where(keep, sorted_expert * cap + pos, e * cap)  # drop slot

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[buf_idx].set(xf[sorted_token])
    buf = buf[:-1].reshape(e, cap, d)

    gt = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(x.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_in"].astype(x.dtype))
    h = jax.nn.silu(gt) * up
    y_e = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(x.dtype))

    yf = y_e.reshape(e * cap, d)
    contrib = jnp.where(keep[:, None],
                        yf[jnp.minimum(buf_idx, e * cap - 1)]
                        * sorted_gate[:, None].astype(x.dtype),
                        0.0)
    y = jnp.zeros((t, d), x.dtype).at[sorted_token].add(contrib)
    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (t * k)
    return MoEOut(y=y.reshape(b, s, d), aux_loss=aux, dropped_frac=dropped)


# ---------------------------------------------------------------------------
# Local-dispatch expert parallelism (§Perf optimization, beyond-paper).
#
# The global-argsort dispatch above lets GSPMD implement token gathers across
# the *data* axis as full-activation all-gathers (~hundreds of GiB/layer for
# dbrx train — see EXPERIMENTS.md §Perf). Local dispatch shard_maps the layer:
# activations stay sharded over the data axes and replicated over `model`;
# each model rank routes its (local) tokens to the experts it owns, computes,
# and a single activation-sized psum over `model` combines the top-k expert
# contributions. Per-layer wire drops from O(T·D·gathers) on the data axis to
# one [T_local, D] all-reduce on the model axis.
# ---------------------------------------------------------------------------

def moe_local(params: dict, cfg: MoEConfig, x: jax.Array, mesh) -> MoEOut:
    """shard_map'd MoE. Falls back to global dispatch when the mesh has no
    usable `model` axis or experts don't divide across it."""
    from jax.sharding import PartitionSpec
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if n_model <= 1 or cfg.n_experts % n_model != 0:
        return moe(params, cfg, x)
    e_loc = cfg.n_experts // n_model
    k = cfg.top_k
    e = cfg.n_experts
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_spec = dp if len(dp) != 1 else dp[0]

    def inner(router, wg, wi, wo, xl):
        b, s, d = xl.shape
        t = b * s
        xf = xl.reshape(t, d)
        logits = jnp.einsum("td,de->te", xf, router.astype(xl.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate, eidx = jax.lax.top_k(probs, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(eidx, e, dtype=jnp.float32),
                              axis=1), axis=0) / k
        aux = e * jnp.sum(me * ce)

        mi = jax.lax.axis_index("model")
        lo = mi * e_loc
        flat_e = eidx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t), k)
        flat_g = gate.reshape(-1)
        is_local = (flat_e >= lo) & (flat_e < lo + e_loc)
        le = jnp.where(is_local, flat_e - lo, e_loc)  # e_loc = drop bucket
        order = jnp.argsort(le)
        se, st_, sg = le[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(se, length=e_loc + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t * k) - starts[se]
        cap = max(1, int(round(t * k / e * cfg.capacity_factor)))
        keep = (pos < cap) & (se < e_loc)
        buf_idx = jnp.where(keep, se * cap + pos, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, d), xl.dtype
                        ).at[buf_idx].set(xf[st_])
        buf = buf[:-1].reshape(e_loc, cap, d)
        gt = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xl.dtype))
        up = jnp.einsum("ecd,edf->ecf", buf, wi.astype(xl.dtype))
        y_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gt) * up,
                         wo.astype(xl.dtype))
        yf = y_e.reshape(e_loc * cap, d)
        contrib = jnp.where(keep[:, None],
                            yf[jnp.minimum(buf_idx, e_loc * cap - 1)]
                            * sg[:, None].astype(xl.dtype), 0.0)
        y = jnp.zeros((t, d), xl.dtype).at[st_].add(contrib)
        y = jax.lax.psum(y, "model")
        dropped = jax.lax.psum(
            jnp.sum((~keep & is_local[order]).astype(jnp.float32)), "model"
        ) / (t * k)
        return y.reshape(b, s, d), aux, dropped

    y, aux, dropped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(PartitionSpec(), PartitionSpec("model", None, None),
                  PartitionSpec("model", None, None),
                  PartitionSpec("model", None, None),
                  PartitionSpec(dp_spec, None, None)),
        out_specs=(PartitionSpec(dp_spec, None, None), PartitionSpec(),
                   PartitionSpec()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_in"], params["w_out"], x)
    return MoEOut(y=y, aux_loss=aux, dropped_frac=dropped)
