"""A DeepSeek-V2 MoE layer (arXiv:2405.04434), run bidirectionally, on one
expert-parallel rank's share of the routed experts.

Pre-norm multi-head latent attention (MLA), then a pre-norm MoE, each added
to the residual.

* MLA with no q LoRA: q = wq h per head, split into a no-RoPE part
  (``qk_nope_head_dim``) and a RoPE part (``qk_rope_head_dim``); the keys
  and values come from a compressed latent, ``c = rmsnorm(wkv_a h)``
  (``kv_lora_rank`` wide), expanded per head by ``wkv_b`` into k's no-RoPE
  part and v (``v_head_dim``); k's RoPE part is one head, also from
  ``wkv_a``, shared by every head.  RoPE is YaRN's (``rope_scaling``): the
  plain inverse frequencies blended with the plain ones over ``factor`` on
  a linear ramp between the correction dims of ``beta_fast`` and
  ``beta_slow`` at ``original_max_position_embeddings``, the rotation
  scaled by mscale(``mscale``) / mscale(``mscale_all_dim``); the softmax
  scale is mscale(``mscale_all_dim``)^2 / sqrt(q/k head dim).
* MoE: a softmax router over all ``n_routed_experts_published`` experts,
  greedy top-``num_experts_per_tok``, the weights renormalised only if
  ``norm_topk_prob``, times ``routed_scaling_factor``.  This rank holds
  ``n_routed_experts`` of them, from ``first_routed_expert_held`` on; each
  is computed on every position and weighted by its routing weight (0
  where not chosen), so nothing is dropped.  The ``n_shared_experts``
  shared experts (one SwiGLU of their summed width) are added once.

Departures from the published (causal) layer: every position attends to
every other, as the denoiser runs it; the routed experts held elsewhere
are left out, as on this rank (``reduced``); RoPE rotates halves of the
rope dims, on the program's weight layout, where the published checkpoint
pairs interleaved dims: the same layer up to a fixed permutation of the
rope columns of ``wq`` and ``wkv_a``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, linear, mlp, mm, rmsnorm


# ---------------------------------------------------------------------------
# YaRN, from the published modelling code's formulas
# ---------------------------------------------------------------------------


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(cfg: dict) -> tuple[np.ndarray, float, float]:
    """(inverse frequencies of the rope dims, softmax scale, rotation
    amplitude)."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    qk_dim = cfg["qk_nope_head_dim"] + dim
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    # the share of each dim kept at its plain frequency
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv_freq = plain / rs["factor"] * (1.0 - mask) + plain * mask
    m_all = _mscale(rs["factor"], rs["mscale_all_dim"]) if rs["mscale_all_dim"] else 1.0
    scale = m_all * m_all / math.sqrt(qk_dim)
    amplitude = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"], rs["mscale_all_dim"])
    return inv_freq.astype(np.float32), scale, amplitude


def _rope(x, inv_freq, amplitude):
    """Rotate-half RoPE over positions 0..S-1; x: (B, S, H, dim)."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return amplitude * jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def mla(p, h, cfg, precision):
    b, s, _ = h.shape
    nh = cfg["num_attention_heads"]
    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    inv_freq, scale, amplitude = yarn(cfg)
    q = linear(p["wq"], h, precision).reshape(b, s, nh, nope + rope_dim)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv_freq, amplitude)
    kv_a = linear(p["wkv_a"], h, precision)
    latent = rmsnorm(p["ckv_norm"]["scale"], kv_a[..., :rank], cfg["rms_norm_eps"])
    k_rope = _rope(kv_a[..., None, rank:], inv_freq, amplitude)[:, :, 0]   # (B, S, rope)
    kv = linear(p["wkv_b"], latent, precision).reshape(b, s, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (mm(q_nope, k_nope, precision, "bqhd,bkhd->bhqk")
              + mm(q_rope, k_rope, precision, "bqhd,bkd->bhqk")) * scale
    w = jax.nn.softmax(scores, axis=-1)
    out = mm(w, v, precision, "bhqk,bkhd->bqhd").reshape(b, s, nh * vd)
    return linear(p["wo"], out, precision)


def routing(p, h, cfg, precision):
    """(B, S, held) routing weight of each held expert, 0 where not chosen."""
    probs = jax.nn.softmax(mm(h, p["router"], precision), axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg["routed_scaling_factor"]
    local = jax.nn.one_hot(ids - cfg["first_routed_expert_held"], cfg["n_routed_experts"])
    return jnp.einsum("bsk,bske->bse", weights, local, precision=HIGHEST)


def moe(p, h, cfg, precision):
    """The held experts' part of the routed result, plus the shared experts."""
    e = p["experts"]
    gate = mm(h, e["wg"], precision, "bsd,edf->ebsf")
    up = mm(h, e["wi"], precision, "bsd,edf->ebsf")
    ys = mm(jax.nn.silu(gate) * up, e["wo"], precision, "ebsf,efd->ebsd")
    routed = jnp.einsum("bse,ebsd->bsd", routing(p, h, cfg, precision), ys,
                        precision=HIGHEST)
    return routed + mlp(p["shared"], h, precision)


def reference(p, x, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["mla"], rmsnorm(p["ln1"]["scale"], x, eps), cfg, precision)
    return x + moe(p["moe"], rmsnorm(p["ln2"]["scale"], x, eps), cfg, precision)


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------


def _qk_v(cfg):
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]


def mla_flops(cfg, rows, seq):
    """MLA's projections, and its score (q/k head dim) and value (v head
    dim) products over every (query, key) pair."""
    d, nh, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    qk, vd = _qk_v(cfg)
    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    proj = d * nh * qk + d * (rank + rope_dim) + rank * nh * (nope + vd) + nh * vd * d
    return 2.0 * rows * seq * proj + 2.0 * rows * nh * seq * seq * (qk + vd)


def assignments(cfg, rows, seq) -> float:
    """Expected (token, expert) assignments to the held experts: each token
    picks ``num_experts_per_tok`` of all the experts, so the held ones take
    their share of the picks."""
    return (rows * seq * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])


def expert_work(cfg, rows, seq, dtype_bytes: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one layer's routed-expert products at the expected
    held load: three products per assignment; each held expert's weights
    read once, and each assignment's input read and output written once."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    a = assignments(cfg, rows, seq)
    flops = 2.0 * a * 3 * d * ff
    nbytes = float(dtype_bytes) * (cfg["n_routed_experts"] * 3 * d * ff + 2 * a * d)
    return flops, nbytes


def moe_flops(cfg, rows, seq):
    """The router over every expert, the shared experts, and the held
    experts at their expected load."""
    d = cfg["hidden_size"]
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return (2.0 * rows * seq * d * cfg["n_routed_experts_published"]
            + 2.0 * rows * seq * 3 * d * shared + expert_work(cfg, rows, seq)[0])


def matmul_flops(cfg, rows, seq):
    return mla_flops(cfg, rows, seq) + moe_flops(cfg, rows, seq)


def flash_calls(cfg, rows, seq, dtype_bytes: int = 2):
    """One call a layer over every head: the score product at the q/k head
    dim and the value product at the v head dim; q and k (every head, as
    MLA expands them), v and the output each read or written once."""
    nh = cfg["num_attention_heads"]
    qk, vd = _qk_v(cfg)
    flops = 2.0 * rows * nh * seq * seq * (qk + vd)
    nbytes = float(dtype_bytes) * rows * seq * nh * (2 * qk + 2 * vd)
    return [(flops, nbytes)]


def mla_keys(pcfg) -> dict:
    """MLA's widths and its YaRN settings, as the program holds them."""
    a = pcfg.mla
    keys = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_scaling")
    if a is None:
        return dict.fromkeys(keys)
    return {
        "kv_lora_rank": a.kv_lora_rank,
        "q_lora_rank": a.q_lora_rank or None,
        "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim,
        "v_head_dim": a.v_head_dim,
        "rope_scaling": {
            "type": "yarn", "factor": a.rope_factor,
            "original_max_position_embeddings": a.rope_original_max_position,
            "beta_fast": a.rope_beta_fast, "beta_slow": a.rope_beta_slow,
            "mscale": a.rope_mscale, "mscale_all_dim": a.rope_mscale_all_dim,
        },
    }


def program_keys(pcfg):
    """MLA's and the MoE's widths, the experts held among them."""
    keys = ("moe_intermediate_size", "n_routed_experts", "n_routed_experts_published",
            "first_routed_expert_held", "num_experts_per_tok", "n_shared_experts",
            "norm_topk_prob")
    m = pcfg.moe
    if m is None:
        return dict(mla_keys(pcfg), **dict.fromkeys(keys))
    return dict(mla_keys(pcfg), **{
        "moe_intermediate_size": m.d_ff_expert,
        "n_routed_experts": m.held,
        "n_routed_experts_published": m.num_experts,
        "first_routed_expert_held": m.first_expert,
        "num_experts_per_tok": m.top_k,
        "n_shared_experts": m.num_shared,
        "norm_topk_prob": m.norm_topk_prob,
    })


#: the expert-parallel ranks the routed experts are spread over (DeepSeek-V2
#: trains with 8-way expert parallelism, arXiv:2405.04434), each holding one
#: range of consecutive experts
EP_RANKS = 8


def router(shape, key):
    """The router (stacked (layers, d, experts)), drawn device-balanced.

    Each column is normal(0, 1/d), then each rank's range of columns is
    centred on its own mean (and scaled back to unit variance), so that the
    logits of every rank's experts sum to zero for any input: no direction
    of the hidden state sends more of the picks to one rank than to another,
    to first order.  DeepSeek-V2 trains its router for this balance (its
    device-level balance loss); a plain random router is not balanced, and
    sends each rank a share of the picks that swings with the draw.  With
    fewer experts than ranks the draw is plain."""
    w = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])
    e = shape[-1]
    if e < EP_RANKS or e % EP_RANKS:
        return w
    g = e // EP_RANKS
    ranks = w.reshape(shape[:-1] + (EP_RANKS, g))
    ranks = (ranks - ranks.mean(axis=-1, keepdims=True)) * np.sqrt(g / (g - 1))
    return ranks.reshape(shape)


def init_leaf(names, shape, key, gain):
    """The routed experts' matrices (``moe/experts/{wi,wg,wo}``, stacked
    (layers, experts, in, out)): normal(0, 1/fan_in), fan_in their input
    width; the router (``moe/router``): :func:`router`."""
    if names[-2] == "experts" and names[-1] in ("wi", "wg", "wo"):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])
    if names[-2:] == ("moe", "router"):
        return router(shape, key)
    raise ValueError(f"no rule for parameter {'/'.join(names)}")
