"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV activations are compressed into a low-rank latent c_kv (kv_lora_rank)
plus a single shared RoPE key head; the cache stores only
(kv_lora_rank + rope_dim) per token — the paper's 93% KV-cache reduction.

Two execution forms:
* train/prefill — expand c_kv to per-head K/V and run standard SDPA
  (no cache reuse, expansion is a single matmul over the sequence).
* decode — the *absorbed* form: W_kb is folded into the query and W_vb into
  the output so attention runs directly in latent space against the
  compressed cache.  This is the production DeepSeek serving trick and our
  paper-faithful baseline for decode shapes.

Both forms share the YaRN RoPE of the rope dims (``rope_inv_freq``) and
the softmax gain ``mscale(mscale_all_dim)**2`` (``softmax_gain``), which
scales the queries so that the attention kernels keep their 1/sqrt(hd).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.attention import resolve_impl, sdpa

Array = jax.Array


def mla_specs(cfg) -> dict:
    a = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qd = a.qk_nope_head_dim + a.qk_rope_head_dim
    return {
        "wq": L.linear_specs(d, h * qd),
        "wkv_a": L.linear_specs(d, a.kv_lora_rank + a.qk_rope_head_dim),
        "ckv_norm": L.rmsnorm_specs(a.kv_lora_rank),
        "wkv_b": L.linear_specs(
            a.kv_lora_rank, h * (a.qk_nope_head_dim + a.v_head_dim)
        ),
        "wo": L.linear_specs(h * a.v_head_dim, d),
    }


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(a, theta: float) -> np.ndarray:
    """Inverse frequencies of the rope dims: YaRN's blend of the plain ones
    (kept for dims that turn more than ``rope_beta_fast`` times over the
    original context) and the plain ones over ``rope_factor`` (for dims
    that turn fewer than ``rope_beta_slow`` times), on a linear ramp
    between the two correction dims."""
    dim = a.qk_rope_head_dim
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if a.rope_factor <= 1:
        return base.astype(np.float32)

    def correction_dim(rotations):
        turns = a.rope_original_max_position / (rotations * 2 * math.pi)
        return dim * math.log(turns) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(a.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(a.rope_beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (base / a.rope_factor * ramp + base * (1 - ramp)).astype(np.float32)


def softmax_gain(a) -> float:
    """YaRN's softmax temperature, mscale(mscale_all_dim)**2 (1 without)."""
    if not a.rope_mscale_all_dim:
        return 1.0
    return _yarn_mscale(a.rope_factor, a.rope_mscale_all_dim) ** 2


def _rope(x, positions, cfg):
    a = cfg.mla
    out = L.apply_rope(x, positions, cfg.rope_theta,
                       inv_freq=rope_inv_freq(a, cfg.rope_theta))
    amp = (_yarn_mscale(a.rope_factor, a.rope_mscale)
           / _yarn_mscale(a.rope_factor, a.rope_mscale_all_dim))
    return out if amp == 1.0 else out * jnp.asarray(amp, out.dtype)


def _project_q(p, x, cfg, positions):
    a = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q = L.linear(p["wq"], x).reshape(b, s, h, a.qk_nope_head_dim + a.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [a.qk_nope_head_dim], axis=-1)
    return q_nope, _rope(q_rope, positions, cfg)


def _compress_kv(p, x, cfg, positions):
    a = cfg.mla
    kv_a = L.linear(p["wkv_a"], x)
    c_kv, k_rope = jnp.split(kv_a, [a.kv_lora_rank], axis=-1)
    c_kv = L.rmsnorm(p["ckv_norm"], c_kv, cfg.norm_eps)
    k_rope = _rope(k_rope[:, :, None, :], positions, cfg)
    return c_kv, k_rope[:, :, 0, :]          # (B,S,r), (B,S,rope)


def mla_train(p, x: Array, cfg, mode: str = "train", cache=None, lengths=None,
              causal: bool = True):
    """Full-sequence MLA (train / prefill). Returns (out, cache).

    ``causal=False`` lets every position attend to every other (the
    diffusion denoiser).  ``lengths`` ((B,) int32) marks right-padding: pad
    keys are masked out of every row's softmax, so a valid position's
    output is the unpadded run's, causal or not."""
    a = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    positions = jnp.arange(s, dtype=jnp.int32)
    kv_mask = None if lengths is None else positions[None, :] < lengths[:, None]

    with jax.named_scope("mla.attention"):
        q_nope, q_rope = _project_q(p, x, cfg, positions)
        c_kv, k_rope = _compress_kv(p, x, cfg, positions)

        kv = L.linear(p["wkv_b"], c_kv).reshape(
            b, s, h, a.qk_nope_head_dim + a.v_head_dim
        )
        k_nope, v = jnp.split(kv, [a.qk_nope_head_dim], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        gain = softmax_gain(a)
        if gain != 1.0:
            q = (q.astype(jnp.float32) * gain).astype(q_nope.dtype)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], q_rope.shape[:2] + (h, a.qk_rope_head_dim))],
            axis=-1,
        )
        # pad v to qk head dim for the shared sdpa, then slice back
        out = sdpa(
            q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, q.shape[-1] - v.shape[-1]))),
            positions, positions,
            window=0, causal=causal, softcap=0.0,
            impl=resolve_impl(cfg, s, s),
            chunk=cfg.attn_chunk, kv_mask=kv_mask,
        )[..., : a.v_head_dim]
        out = L.linear(p["wo"], out.reshape(b, s, -1))

    if mode == "prefill":
        assert cache is not None
        slots = cache["ckv"].shape[1]
        take = min(s, slots)
        pos_arr = positions[-take:]
        cache = {
            "ckv": jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], c_kv[:, -take:].astype(cache["ckv"].dtype), 0, axis=1
            ),
            "krope": jax.lax.dynamic_update_slice_in_dim(
                cache["krope"], k_rope[:, -take:].astype(cache["krope"].dtype), 0, axis=1
            ),
            "pos": jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], pos_arr, 0, axis=0
            ),
        }
    return out, cache


def mla_decode(p, x: Array, cfg, cache: dict, pos: Array):
    """Absorbed-form single-token decode against the compressed cache."""
    a = cfg.mla
    b, s, _ = x.shape  # s == 1
    h = cfg.num_heads
    positions = jnp.atleast_1d(jnp.asarray(pos, jnp.int32))

    q_nope, q_rope = _project_q(p, x, cfg, positions)        # (B,1,H,*)
    c_kv_new, k_rope_new = _compress_kv(p, x, cfg, positions)

    slots = cache["ckv"].shape[1]
    slot = positions[0] % slots
    cache = {
        "ckv": jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_kv_new.astype(cache["ckv"].dtype), slot, axis=1
        ),
        "krope": jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope_new.astype(cache["krope"].dtype), slot, axis=1
        ),
        "pos": jax.lax.dynamic_update_index_in_dim(
            cache["pos"], positions[0], slot, axis=0
        ),
    }

    wkv_b = p["wkv_b"]["w"].reshape(
        a.kv_lora_rank, h, a.qk_nope_head_dim + a.v_head_dim
    )
    w_kb = wkv_b[..., : a.qk_nope_head_dim]     # (r, H, nope)
    w_vb = wkv_b[..., a.qk_nope_head_dim :]     # (r, H, v)

    # absorb W_kb into the query -> latent-space query
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kb.astype(q_nope.dtype))

    ckv = cache["ckv"]                          # (B, S, r)
    krope = cache["krope"]                      # (B, S, rope)
    scale = (a.qk_nope_head_dim + a.qk_rope_head_dim) ** -0.5 * softmax_gain(a)
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, ckv.astype(q_lat.dtype))
        + jnp.einsum("bshr,btr->bhst", q_rope, krope.astype(q_rope.dtype))
    ).astype(jnp.float32) * scale
    valid = cache["pos"] >= 0
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", w.astype(ckv.dtype), ckv)   # latent ctx
    out = jnp.einsum("bshr,rhv->bshv", ctx, w_vb.astype(ctx.dtype))
    return L.linear(p["wo"], out.reshape(b, s, -1)), cache


def mla_init_cache(cfg, batch: int, slots: int, dtype):
    a = cfg.mla
    return {
        "ckv": jnp.zeros((batch, slots, a.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, slots, a.qk_rope_head_dim), dtype),
        "pos": jnp.full((slots,), -1, jnp.int32),
    }


def mla_abstract_cache(cfg, batch: int, slots: int, dtype):
    a = cfg.mla
    return {
        "ckv": jax.ShapeDtypeStruct((batch, slots, a.kv_lora_rank), dtype),
        "krope": jax.ShapeDtypeStruct((batch, slots, a.qk_rope_head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((slots,), jnp.int32),
    }
