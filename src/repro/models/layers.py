"""Common layers + the parameter-spec system.

Parameters are plain pytrees (nested dicts of arrays).  Each layer exposes a
``*_specs`` function returning a matching pytree of :class:`P` (shape +
initializer), from which we derive either real initialized params (smoke
tests, training) or ``jax.ShapeDtypeStruct`` stand-ins (the multi-pod
dry-run, which must never allocate).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class P:
    """Parameter spec: shape + init rule. fan_in for scaled-normal init."""

    shape: tuple[int, ...]
    init: str = "fan_in"  # fan_in | zeros | ones | normal | embed
    scale: float = 1.0

    def initialize(self, key: jax.Array, dtype) -> Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "normal":
            return self.scale * jax.random.normal(key, self.shape, dtype)
        if self.init == "embed":
            return jax.random.normal(key, self.shape, dtype) * 0.02 * self.scale
        if self.init == "fan_in":
            fan_in = self.shape[0] if len(self.shape) >= 2 else 1
            std = self.scale / math.sqrt(max(fan_in, 1))
            return jax.random.normal(key, self.shape, dtype) * std
        raise ValueError(f"unknown init {self.init!r}")


def is_spec(x) -> bool:
    return isinstance(x, P)


def init_params(specs, key: jax.Array, dtype=jnp.float32):
    """Initialize a pytree of P into a pytree of arrays."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    arrs = [s.initialize(k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrs)


def abstract_params(specs, dtype=jnp.float32):
    """P pytree -> ShapeDtypeStruct pytree (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs, is_leaf=is_spec
    )


def stack_specs(specs, n: int):
    """Prepend a layer dimension of size n to every spec (for lax.scan)."""
    return jax.tree.map(
        lambda s: P((n,) + s.shape, s.init, s.scale), specs, is_leaf=is_spec
    )


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def rmsnorm_specs(d: int) -> dict:
    return {"scale": P((d,), "ones")}


def rmsnorm(p, x: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_specs(d: int) -> dict:
    return {"scale": P((d,), "ones"), "bias": P((d,), "zeros")}


def layernorm(p, x: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Linear / MLP
# ---------------------------------------------------------------------------


def linear_specs(d_in: int, d_out: int, bias: bool = False, scale=1.0) -> dict:
    s = {"w": P((d_in, d_out), "fan_in", scale)}
    if bias:
        s["b"] = P((d_out,), "zeros")
    return s


def linear(p, x: Array) -> Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def mlp_specs(d: int, d_ff: int, act: str = "silu") -> dict:
    if act in ("silu", "gelu"):  # gated
        return {
            "wi": linear_specs(d, d_ff),
            "wg": linear_specs(d, d_ff),
            "wo": linear_specs(d_ff, d, scale=1.0),
        }
    return {"wi": linear_specs(d, d_ff), "wo": linear_specs(d_ff, d)}


def mlp(p, x: Array, act: str = "silu") -> Array:
    if act == "silu":
        h = jax.nn.silu(linear(p["wg"], x)) * linear(p["wi"], x)
    elif act == "gelu":
        h = jax.nn.gelu(linear(p["wg"], x)) * linear(p["wi"], x)
    else:  # gelu_plain
        h = jax.nn.gelu(linear(p["wi"], x))
    return linear(p["wo"], h)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: Array, positions: Array, theta: float, inv_freq=None) -> Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int.
    ``inv_freq`` ((head_dim/2,)) replaces the plain frequencies (YaRN)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None else inv_freq  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Time embedding (diffusion conditioning)
# ---------------------------------------------------------------------------


def sinusoidal_time_embed(t: Array, dim: int, max_period: float = 1e4) -> Array:
    """t: scalar or (B,) in [0, 1] -> (B?, dim) embedding."""
    t = jnp.asarray(t, jnp.float32) * 1000.0  # scale to DDPM-like range
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    ang = t[..., None] * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def time_mlp_specs(d_model: int, d_time: int = 256) -> dict:
    return {
        "w1": linear_specs(d_time, d_model, bias=True),
        "w2": linear_specs(d_model, d_model, bias=True),
    }


def time_mlp(p, t: Array, d_time: int = 256) -> Array:
    h = sinusoidal_time_embed(t, d_time)
    h = jax.nn.silu(linear(p["w1"], h))
    return linear(p["w2"], h)


# ---------------------------------------------------------------------------
# Causal depthwise conv (Mamba / xLSTM front conv)
# ---------------------------------------------------------------------------


def causal_conv_specs(d: int, width: int) -> dict:
    return {"w": P((width, d), "normal", 0.1), "b": P((d,), "zeros")}


def causal_conv1d(p, x: Array, state: Array | None = None):
    """Depthwise causal conv. x: (B, S, d).

    Returns (y, new_state) where state is the last (width-1) inputs — the
    decode-time carry.
    """
    w = p["w"].astype(x.dtype)  # (W, d)
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S + W - 1, d)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i] for i in range(width)
    ) + p["b"].astype(x.dtype)
    new_state = xp[:, -(width - 1) :, :] if width > 1 else pad
    return y, new_state
