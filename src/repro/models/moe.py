"""Mixture-of-Experts layer (Mixtral top-2, DeepSeek shared+routed top-6).

The router scores every expert (``num_experts``); a device holds the
experts ``first_expert .. first_expert + experts_held - 1`` (all of them
by default) and computes their part of the result.  That is one rank of
expert parallelism, run without its exchange: on one chip the other
experts' assignments are left to the ranks that would hold them.

Dispatch strategies:

* ``dropless`` — every assignment to a held expert runs: assignments are
  sorted by expert and the expert FFN runs as three ``jax.lax.ragged_dot``
  products with per-expert group sizes (on TPU their time follows the held
  rows, not the operand's), then each token's k outputs are gathered back
  and summed with the top-k weights.  No capacity, so a token's output depends on its own
  routing alone, never on its batch-mates or on padding (DeepSeek-V2).
* ``dropping`` — capacity-based token dispatch realized with
  scatter/gather per batch group (TPU adaptation: no giant one-hot dispatch
  einsum, so compiled FLOPs stay honest — dispatch moves bytes, the expert
  FFN does the FLOPs).  Tokens over capacity are dropped (residual passes
  through), the standard TPU training recipe.  Holds every expert.
* ``dense_mix`` — every held expert runs on every token, outputs mixed by
  router probs.  O(E) FLOPs; used as the correctness oracle in tests and
  for tiny smoke configs.

Router math is float32 throughout (bf16 routers destabilize top-k).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.parallel.ctx import constrain_dims

Array = jax.Array


def moe_specs(cfg) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ff = m.d_ff_expert
    e = m.held
    s = {
        "router": L.P((d, m.num_experts), "fan_in"),
        "experts": {
            "wi": L.P((e, d, ff), "fan_in"),
            "wg": L.P((e, d, ff), "fan_in"),
            "wo": L.P((e, ff, d), "fan_in"),
        },
    }
    if m.num_shared:
        s["shared"] = L.mlp_specs(d, ff * m.num_shared, "silu")
    return s


def _router(p, x: Array, m) -> tuple[Array, Array, dict]:
    """Return (weights (..., k), ids (..., k), aux losses)."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance loss + router z-loss
    e = m.num_experts
    density = jnp.mean(
        jax.nn.one_hot(ids, e, dtype=jnp.float32).sum(axis=-2), axis=tuple(range(ids.ndim - 1))
    ) / m.top_k
    mean_prob = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = {
        "moe_aux": e * jnp.sum(density * mean_prob),
        "moe_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return weights, ids, aux


def _expert_ffn(experts: dict, xs: Array) -> Array:
    """xs: (E, C, d) -> (E, C, d), batched over experts."""
    wi = experts["wi"].astype(xs.dtype)
    wg = experts["wg"].astype(xs.dtype)
    wo = experts["wo"].astype(xs.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wg)) * jnp.einsum(
        "ecd,edf->ecf", xs, wi
    )
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _expert_ffn_grouped(experts: dict, xs: Array) -> Array:
    """xs: (G, E, C, d) -> (G, E, C, d).  Layouts pinned so GSPMD keeps the
    token dims on the data axes and the expert hidden dim on the model axis
    (without this, the d-contraction gets sharded and every MoE layer
    all-reduces a (E, C, ff)-sized partial sum — see EXPERIMENTS.md §Perf).
    """
    wi = experts["wi"].astype(xs.dtype)
    wg = experts["wg"].astype(xs.dtype)
    wo = experts["wo"].astype(xs.dtype)
    # possible here; constrain token dims only and leave E/ff to the weights.
    xs = constrain_dims(xs, ("dp", None, None, None))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xs, wg)) * jnp.einsum(
        "gecd,edf->gecf", xs, wi
    )
    out = jnp.einsum("gecf,efd->gecd", h, wo)
    return constrain_dims(out, ("dp", None, None, None))


def _dispatch_group(p, x: Array, m) -> tuple[Array, tuple, dict]:
    """Routing + capacity scatter for one token group. x: (S, d).
    Returns (buf (E, cap+1, d), combine-metadata, aux)."""
    s, d = x.shape
    k, e = m.top_k, m.num_experts
    cap = max(int(s * k / e * m.capacity_factor), 1)

    weights, ids, aux = _router(p, x, m)          # (S, k)
    flat_e = ids.reshape(-1)                      # (S*k,)
    flat_w = weights.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(s), k)

    # rank of each assignment within its expert (stable by token order)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank_sorted = jnp.arange(s * k) - start[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)

    keep = rank < cap
    slot = jnp.where(keep, rank, cap)             # overflow -> dump slot

    # scatter tokens into (E, cap+1, d); dump slot discarded
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[flat_e, slot].set(x[tok_idx], mode="drop")
    return buf, (flat_e, slot, keep, flat_w, tok_idx), aux


def _combine_group(out_buf: Array, meta: tuple, s: int) -> Array:
    """Gather expert outputs back to token order with top-k weights."""
    flat_e, slot, keep, flat_w, tok_idx = meta
    cap = out_buf.shape[1]
    d = out_buf.shape[-1]
    gathered = out_buf[flat_e, jnp.minimum(slot, cap - 1)]   # (S*k, d)
    gathered = gathered * keep[:, None].astype(gathered.dtype)
    return jnp.zeros((s, d), out_buf.dtype).at[tok_idx].add(
        gathered * flat_w[:, None].astype(gathered.dtype)
    )


def _held_ids(ids: Array, m) -> Array:
    """Each assignment's expert among the held ones, ``m.held`` where the
    expert is held elsewhere."""
    local = ids - m.first_expert
    return jnp.where((local >= 0) & (local < m.held), local, m.held)


def _dense_mix(p, x: Array, m) -> tuple[Array, dict]:
    """Reference: run every held expert on all tokens. x: (..., d)."""
    weights, ids, aux = _router(p, x, m)
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    outs = _expert_ffn(
        p["experts"], jnp.broadcast_to(flat[None], (m.held,) + flat.shape)
    )                                             # (H, N, d)
    # (N, H) routing weight of each held expert, 0 where not chosen
    gate = jnp.einsum(
        "nk,nkh->nh",
        weights.reshape(flat.shape[0], -1),
        jax.nn.one_hot(_held_ids(ids, m).reshape(flat.shape[0], -1), m.held),
    )
    mix = jnp.einsum("nh,hnd->nd", gate.astype(x.dtype), outs)
    return mix.reshape(x.shape), aux


def _dropless(p, x: Array, m) -> tuple[Array, dict]:
    """Every assignment to a held expert, as ragged products. x: (..., d)."""
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    n, k = flat.shape[0], m.top_k
    with jax.named_scope("moe.route"):
        weights, ids, aux = _router(p, flat, m)   # (N, k)
        expert = _held_ids(ids, m)                # (N, k)
        order = jnp.argsort(expert.reshape(-1), stable=True)  # others sort last
        group_sizes = jnp.bincount(expert.reshape(-1), length=m.held + 1)
    with jax.named_scope("moe.experts"):
        e = p["experts"]
        gs = group_sizes[: m.held].astype(jnp.int32)
        xs = flat[order // k]                     # (N*k, d), grouped by expert
        h = jax.nn.silu(
            jax.lax.ragged_dot(xs, e["wg"].astype(x.dtype), gs)
        ) * jax.lax.ragged_dot(xs, e["wi"].astype(x.dtype), gs)
        ys = jax.lax.ragged_dot(h, e["wo"].astype(x.dtype), gs)
        # back to each token's k slots; rows past the held groups are the
        # backend's to fill (the reference lowering zeroes them), so they
        # are selected away rather than trusted to be 0
        ys = ys[jnp.argsort(order)].reshape(n, k, d).astype(jnp.float32)
        held = (expert < m.held)[..., None]
        out = jnp.sum(jnp.where(held, ys * weights[..., None], 0.0), axis=1)
    return out.astype(x.dtype).reshape(x.shape), aux


def moe_ffn(p, x: Array, cfg) -> tuple[Array, dict]:
    """x: (B, S, d) -> (B, S, d), plus aux losses."""
    m = cfg.moe
    b, s, d = x.shape
    if m.dispatch == "dropless":
        out, aux = _dropless(p, x, m)
    elif m.dispatch == "dense_mix":
        out, aux = _dense_mix(p, x, m)
    elif m.dispatch == "dropping":
        # split long sequences into dispatch groups so the (E, C, d)
        # capacity buffer stays bounded (§Perf iteration B3)
        g = min(m.dispatch_group, s) if s % min(m.dispatch_group, s) == 0 else s
        ng = b * (s // g)
        xg = x.reshape(ng, g, d)
        # vmap carries only the index math; the expert FFN runs as one
        # grouped einsum with pinned layouts (see _expert_ffn_grouped)
        buf, meta, aux_stack = jax.vmap(
            lambda xx: _dispatch_group(p, xx, m)
        )(xg)
        buf = constrain_dims(buf, ("dp", None, None, None))
        cap = buf.shape[2] - 1
        out_buf = _expert_ffn_grouped(p["experts"], buf[:, :, :cap])
        out = jax.vmap(lambda ob, mt: _combine_group(ob, mt, g))(out_buf, meta)
        out = out.reshape(b, s, d)
        aux = jax.tree.map(jnp.mean, aux_stack)
    else:
        raise ValueError(f"unknown MoE dispatch {m.dispatch!r}")
    if m.num_shared:
        with jax.named_scope("moe.shared"):
            out = out + L.mlp(p["shared"], x, "silu")
    return out, aux
