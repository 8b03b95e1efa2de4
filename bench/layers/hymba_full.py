"""A Hymba layer with full attention (arXiv:2411.13676), run bidirectionally.

Attention heads and Mamba heads run side by side on the same pre-normed
input; their outputs are normed separately and averaged into the residual,
then a pre-norm SwiGLU MLP.  The Mamba heads are a selective scan written
as a plain ``lax.scan`` over positions.  ``hymba_swa`` is the same layer
with windowed attention and imports this module.

Departures from the published (causal) layer: attention sees every
position, as the denoiser runs it; the 128 meta tokens and the cross-layer
KV sharing are left out, as in the program (the configuration's
``reduced``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import HIGHEST, attention, linear, mlp, rmsnorm


def mamba(p, h, cfg, precision):
    """Selective SSM: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t, y_t = C_t h_t."""
    n = cfg["mamba_d_state"]
    dtr = cfg["mamba_dt_rank"]
    x, z = jnp.split(linear(p["in_proj"], h, precision), 2, axis=-1)
    width = p["conv"]["w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    x = sum(xp[:, i : i + s] * p["conv"]["w"][i] for i in range(width))
    x = jax.nn.silu(x + p["conv"]["b"])
    proj = linear(p["x_proj"], x, precision)
    dt, bmat, cmat = jnp.split(proj, [dtr, dtr + n], axis=-1)
    dt = jax.nn.softplus(linear(p["dt_proj"], dt, precision))       # (B,S,di)
    a = -jnp.exp(p["A_log"])                                         # (di,N)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp                   # (B,di) (B,di) (B,N) (B,N)
        state = jnp.exp(dt_t[..., None] * a) * state + (
            (dt_t * x_t)[..., None] * b_t[:, None, :]
        )
        return state, jnp.einsum("bdn,bn->bd", state, c_t, precision=HIGHEST)

    state0 = jnp.zeros((x.shape[0], x.shape[2], n), jnp.float32)
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, x, bmat, cmat))
    _, ys = jax.lax.scan(step, state0, seq, unroll=8)
    y = jnp.moveaxis(ys, 0, 1) + x * p["D"]
    return linear(p["out_proj"], y * jax.nn.silu(z), precision)


def layer(p, x, cfg, window, precision):
    eps = cfg["rms_norm_eps"]
    h = rmsnorm(p["ln1"]["scale"], x, eps)
    attn = attention(p["attn"], h, cfg, window, precision)
    ssm = mamba(p["mamba"], h, cfg, precision)
    x = x + 0.5 * (
        rmsnorm(p["attn_norm"]["scale"], attn, eps)
        + rmsnorm(p["mamba_norm"]["scale"], ssm, eps)
    )
    return x + mlp(p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps), precision)


def layer_flops(cfg, rows, seq, window):
    """Attention, the MLP, and the Mamba heads' projections and depthwise
    conv; not the scan's elementwise recurrence."""
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    n, dtr = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    mamba_params = d * 2 * di + di * (dtr + 2 * n) + dtr * di + di * d + cfg["mamba_d_conv"] * di
    return (flops.attention_flops(cfg, rows, seq, window) + flops.mlp_flops(cfg, rows, seq)
            + 2.0 * rows * seq * mamba_params)


def program_keys(pcfg):
    """The Mamba heads' widths; all None (so refused) where the program
    runs no Mamba heads."""
    ssm = pcfg.ssm
    if ssm is None:
        return dict.fromkeys(("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"))
    return {
        "mamba_d_state": ssm.state_dim,
        "mamba_d_conv": ssm.conv_dim,
        "mamba_expand": ssm.expand,
        "mamba_dt_rank": ssm.dt_rank or -(-pcfg.d_model // 16),
    }


def reference(p, x, cfg, precision):
    return layer(p, x, cfg, 0, precision)


def matmul_flops(cfg, rows, seq):
    return layer_flops(cfg, rows, seq, 0)


def flash_calls(cfg, rows, seq):
    return [flops.flash_attention_call(cfg, rows, seq, 0)]
