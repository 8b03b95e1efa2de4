"""Block registry: every architecture family is a stack of these blocks.

A block is (specs, apply, init_cache, abstract_cache) with a uniform apply
signature so homogeneous segments can ``lax.scan`` over stacked params:

    apply(p, x, cache, ctx) -> (x, new_cache, aux)

``ctx`` is a :class:`BlockCtx` of static-ish values (mode, window override,
decode position, encoder states).  ``aux`` is a fixed-schema dict of scalars
(MoE losses) so scans stay homogeneous.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BlockCtx:
    mode: str                      # train | prefill | decode
    pos: Any = None                # decode position (traced scalar)
    causal: bool = True            # False for diffusion-LM denoising
    window_override: int = -1      # -1: use block default; 0: full; >0: window
    protected: int = 0             # cache slots never evicted (meta tokens)
    enc_out: Any = None            # whisper encoder states (B, F, d)
    lengths: Any = None            # (B,) valid seq lengths of a right-padded
                                   # batch (diffusion-LM mixed-seq-len path);
                                   # attention blocks mask pad keys


def zero_aux() -> dict:
    return {"moe_aux": jnp.float32(0.0), "moe_z": jnp.float32(0.0)}


def _window(cfg, ctx: BlockCtx, default: int) -> int:
    return default if ctx.window_override < 0 else ctx.window_override


# ---------------------------------------------------------------------------
# dense (llama/qwen/deepseek-67b/minitron/paligemma) and moe (mixtral)
# ---------------------------------------------------------------------------


def dense_specs(cfg) -> dict:
    return {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "attn": A.attention_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
    }


def dense_apply(p, x, cache, ctx: BlockCtx, cfg):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_out, cache = A.attention(
        p["attn"], h, cfg,
        mode=ctx.mode, cache=cache, pos=ctx.pos,
        window=_window(cfg, ctx, cfg.sliding_window),
        protected=ctx.protected, causal=ctx.causal, lengths=ctx.lengths,
    )
    x = x + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h, cfg.mlp_act)
    return x, cache, zero_aux()


def moe_specs_(cfg) -> dict:
    return {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "attn": A.attention_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "moe": MOE.moe_specs(cfg),
    }


def moe_apply(p, x, cache, ctx: BlockCtx, cfg):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_out, cache = A.attention(
        p["attn"], h, cfg,
        mode=ctx.mode, cache=cache, pos=ctx.pos,
        window=_window(cfg, ctx, cfg.sliding_window),
        protected=ctx.protected, causal=ctx.causal, lengths=ctx.lengths,
    )
    x = x + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    ffn_out, aux = MOE.moe_ffn(p["moe"], h, cfg)
    return x + ffn_out, cache, {**zero_aux(), **aux}


# ---------------------------------------------------------------------------
# mla_dense / mla_moe (deepseek-v2-lite: a leading dense layer, then MoE)
# ---------------------------------------------------------------------------


def _mla(p, h, cache, ctx: BlockCtx, cfg):
    if ctx.mode == "decode":
        return MLA.mla_decode(p, h, cfg, cache, ctx.pos)
    return MLA.mla_train(
        p, h, cfg, ctx.mode, cache, lengths=ctx.lengths, causal=ctx.causal
    )


def mla_dense_specs(cfg) -> dict:
    return {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "mla": MLA.mla_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
    }


def mla_dense_apply(p, x, cache, ctx: BlockCtx, cfg):
    attn_out, cache = _mla(p["mla"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cache, ctx, cfg)
    x = x + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.mlp(p["mlp"], h, cfg.mlp_act), cache, zero_aux()


def mla_moe_specs(cfg) -> dict:
    return {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "mla": MLA.mla_specs(cfg),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "moe": MOE.moe_specs(cfg),
    }


def mla_moe_apply(p, x, cache, ctx: BlockCtx, cfg):
    attn_out, cache = _mla(p["mla"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cache, ctx, cfg)
    x = x + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    ffn_out, aux = MOE.moe_ffn(p["moe"], h, cfg)
    return x + ffn_out, cache, {**zero_aux(), **aux}


# ---------------------------------------------------------------------------
# xLSTM blocks (pre-norm residual handled inside SSM module)
# ---------------------------------------------------------------------------


def mlstm_apply(p, x, cache, ctx: BlockCtx, cfg):
    mode = ctx.mode
    x, state = SSM.mlstm_block(p, x, cfg, state=cache, mode=mode)
    return x, state if cache is not None else None, zero_aux()


def slstm_apply(p, x, cache, ctx: BlockCtx, cfg):
    x, state = SSM.slstm_block(p, x, cfg, state=cache, mode=ctx.mode)
    return x, state if cache is not None else None, zero_aux()


# ---------------------------------------------------------------------------
# hymba: parallel attention + mamba heads, then MLP
# ---------------------------------------------------------------------------


def hymba_specs(cfg) -> dict:
    return {
        "ln1": L.rmsnorm_specs(cfg.d_model),
        "attn": A.attention_specs(cfg),
        "mamba": SSM.mamba_specs(cfg),
        "attn_norm": L.rmsnorm_specs(cfg.d_model),
        "mamba_norm": L.rmsnorm_specs(cfg.d_model),
        "ln2": L.rmsnorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_act),
    }


def _hymba_apply(p, x, cache, ctx: BlockCtx, cfg, window: int):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_cache = None if cache is None else cache["attn"]
    ssm_state = None if cache is None else cache["ssm"]
    attn_out, attn_cache = A.attention(
        p["attn"], h, cfg,
        mode=ctx.mode, cache=attn_cache, pos=ctx.pos,
        window=_window(cfg, ctx, window), protected=ctx.protected,
        causal=ctx.causal, lengths=ctx.lengths,
    )
    mamba_out, ssm_state = SSM.mamba(
        p["mamba"], h, cfg,
        state=ssm_state if cache is not None else None, mode=ctx.mode,
    )
    # Hymba fuses the two head groups by averaging their normalized outputs
    fused = 0.5 * (
        L.rmsnorm(p["attn_norm"], attn_out, cfg.norm_eps)
        + L.rmsnorm(p["mamba_norm"], mamba_out, cfg.norm_eps)
    )
    x = x + fused
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h, cfg.mlp_act)
    new_cache = None if cache is None else {"attn": attn_cache, "ssm": ssm_state}
    return x, new_cache, zero_aux()


def hymba_swa_apply(p, x, cache, ctx, cfg):
    return _hymba_apply(p, x, cache, ctx, cfg, cfg.sliding_window)


def hymba_full_apply(p, x, cache, ctx, cfg):
    return _hymba_apply(p, x, cache, ctx, cfg, 0)


# ---------------------------------------------------------------------------
# whisper: encoder block (bidirectional) and decoder block (self + cross)
# ---------------------------------------------------------------------------


def enc_specs(cfg) -> dict:
    return {
        "ln1": L.layernorm_specs(cfg.d_model),
        "attn": A.attention_specs(cfg),
        "ln2": L.layernorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, "gelu_plain"),
    }


def enc_apply(p, x, cache, ctx: BlockCtx, cfg):
    h = L.layernorm(p["ln1"], x, cfg.norm_eps)
    attn_out, _ = A.attention(
        p["attn"], h, cfg, mode="train", cache=None, causal=False,
        lengths=ctx.lengths,
    )
    x = x + attn_out
    h = L.layernorm(p["ln2"], x, cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h, "gelu_plain")
    return x, cache, zero_aux()


def xdec_specs(cfg) -> dict:
    return {
        "ln1": L.layernorm_specs(cfg.d_model),
        "self_attn": A.attention_specs(cfg),
        "ln_x": L.layernorm_specs(cfg.d_model),
        "cross_attn": A.attention_specs(cfg),
        "ln2": L.layernorm_specs(cfg.d_model),
        "mlp": L.mlp_specs(cfg.d_model, cfg.d_ff, "gelu_plain"),
    }


def xdec_apply(p, x, cache, ctx: BlockCtx, cfg):
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    self_cache = None if cache is None else cache["self"]
    h = L.layernorm(p["ln1"], x, cfg.norm_eps)
    attn_out, self_cache = A.attention(
        p["self_attn"], h, cfg,
        mode=ctx.mode, cache=self_cache, pos=ctx.pos,
        window=_window(cfg, ctx, cfg.sliding_window),
        lengths=ctx.lengths,
    )
    x = x + attn_out

    ek = ev = None
    if ctx.mode == "decode":
        ek, ev = cache["xk"], cache["xv"]
    elif ctx.enc_out is not None:
        enc = ctx.enc_out
        b, f, _ = enc.shape
        ek = L.linear(p["cross_attn"]["wk"], enc).reshape(b, f, kvh, hd)
        ev = L.linear(p["cross_attn"]["wv"], enc).reshape(b, f, kvh, hd)
    if ek is not None:  # no encoder context => decoder-only (diffusion-LM)
        h = L.layernorm(p["ln_x"], x, cfg.norm_eps)
        xo, _ = A.attention(
            p["cross_attn"], h, cfg, mode=ctx.mode, pos=ctx.pos, cross_kv=(ek, ev)
        )
        x = x + xo

    h = L.layernorm(p["ln2"], x, cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h, "gelu_plain")

    new_cache = cache
    if cache is not None:
        new_cache = dict(cache, self=self_cache)
        if ctx.mode == "prefill":
            new_cache["xk"], new_cache["xv"] = ek, ev
    return x, new_cache, zero_aux()


# ---------------------------------------------------------------------------
# cache factories
# ---------------------------------------------------------------------------


def _attn_cache(cfg, batch, slots, dtype, abstract):
    fn = A.abstract_cache if abstract else A.init_cache
    return fn(
        batch, slots, cfg.num_kv_heads, cfg.resolved_head_dim, dtype,
        quant=(cfg.kv_quant == "int8"),
    )


def _mla_cache(cfg, batch, slots, dtype, abstract):
    fn = MLA.mla_abstract_cache if abstract else MLA.mla_init_cache
    return fn(cfg, batch, slots, dtype)


def _ssm_cache(cfg, batch, slots, dtype, abstract):
    if abstract:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            SSM.mamba_init_state(cfg, batch, dtype),
        )
    return SSM.mamba_init_state(cfg, batch, dtype)


def _mlstm_cache(cfg, batch, slots, dtype, abstract):
    st = SSM.mlstm_init_state(cfg, batch, dtype)
    if abstract:
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    return st


def _slstm_cache(cfg, batch, slots, dtype, abstract):
    st = SSM.slstm_init_state(cfg, batch, dtype)
    if abstract:
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    return st


def _hymba_cache(cfg, batch, slots, dtype, abstract):
    return {
        "attn": _attn_cache(cfg, batch, slots, dtype, abstract),
        "ssm": _ssm_cache(cfg, batch, slots, dtype, abstract),
    }


def _xdec_cache(cfg, batch, slots, dtype, abstract):
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    f = cfg.frontend.num_positions
    shape = (batch, f, kvh, hd)
    if abstract:
        xk = xv = jax.ShapeDtypeStruct(shape, dtype)
    else:
        xk = jnp.zeros(shape, dtype)
        xv = jnp.zeros(shape, dtype)
    return {
        "self": _attn_cache(cfg, batch, slots, dtype, abstract),
        "xk": xk,
        "xv": xv if abstract else jnp.zeros(shape, dtype),
    }


@dataclasses.dataclass(frozen=True)
class BlockDef:
    specs: Callable
    apply: Callable
    cache: Callable | None  # (cfg, batch, slots, dtype, abstract) -> pytree


BLOCKS: dict[str, BlockDef] = {
    "dense": BlockDef(dense_specs, dense_apply, _attn_cache),
    "moe": BlockDef(moe_specs_, moe_apply, _attn_cache),
    "mla_dense": BlockDef(mla_dense_specs, mla_dense_apply, _mla_cache),
    "mla_moe": BlockDef(mla_moe_specs, mla_moe_apply, _mla_cache),
    "mlstm": BlockDef(SSM.mlstm_specs, mlstm_apply, _mlstm_cache),
    "slstm": BlockDef(SSM.slstm_specs, slstm_apply, _slstm_cache),
    "hymba_swa": BlockDef(hymba_specs, hymba_swa_apply, _hymba_cache),
    "hymba_full": BlockDef(hymba_specs, hymba_full_apply, _hymba_cache),
    "enc": BlockDef(enc_specs, enc_apply, None),
    "xdec": BlockDef(xdec_specs, xdec_apply, _xdec_cache),
}
