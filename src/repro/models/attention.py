"""GQA attention: projections, KV cache, and three SDPA implementations.

* ``naive``   — materializes (Sq, Sk) scores; smoke tests / short seq.
* ``chunked`` — XLA-native streaming-softmax over KV chunks (lax.scan).
  This is the dry-run / long-context path: memory is O(Sq * chunk) and the
  FLOPs are what a TPU flash kernel would do, so ``cost_analysis`` stays
  honest on CPU where a Pallas TPU kernel cannot compile.
* ``pallas``  — the Pallas flash kernel in :mod:`repro.kernels` (compiled
  on TPU, interpret mode elsewhere).  ``auto`` resolves to it on TPU
  (:func:`resolve_impl`).

Masking is positional: every key slot carries an absolute position (-1 for
invalid ring-buffer slots), so full causal, sliding-window, and ring-buffer
decode all share one code path.
"""

from __future__ import annotations

import functools
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import layers as L

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# fast-path fallback: loud, observable, and the only place impl is rewritten
# ---------------------------------------------------------------------------

#: callables ``(impl, reason) -> None`` notified whenever sdpa rewrites a
#: requested fast impl to chunked.  The serving executor registers one to
#: drive the ``sampler_masked_fallback_total`` counter — the permanent
#: canary that fused mixed-length traffic regressed off the fast kernels.
#: Observers fire at trace time, so each count is a compiled-program
#: materialization that runs the slow path, not a per-request count.
_fallback_observers: list[Callable[[str, str], None]] = []
_warned_fallbacks: set[tuple[str, str]] = set()


def register_fallback_observer(fn: Callable[[str, str], None]) -> Callable:
    _fallback_observers.append(fn)
    return fn


def unregister_fallback_observer(fn: Callable[[str, str], None]) -> None:
    try:
        _fallback_observers.remove(fn)
    except ValueError:
        pass


def _fallback_to_chunked(impl: str, reason: str) -> str:
    """Rewrite a requested fast impl to ``chunked``: warn once per
    (impl, reason) and notify every registered observer.  Any config that
    still can't ride the fast kernels goes through here — never an inline
    silent rewrite."""
    key = (impl, reason)
    if key not in _warned_fallbacks:
        _warned_fallbacks.add(key)
        warnings.warn(
            f"sdpa: requested impl={impl!r} unavailable ({reason}); "
            "falling back to chunked SDPA. This trades the fused "
            "fast-attention kernel for the slow path — check "
            "sampler_masked_fallback_total if this is serving traffic.",
            RuntimeWarning,
            stacklevel=3,
        )
    for fn in list(_fallback_observers):
        fn(impl, reason)
    return "chunked"


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------


def _kv_mask_bias(kv_mask: Array) -> Array:
    """(B, Sk) per-row key-validity mask -> additive bias.

    Valid keys get an exact ``0.0`` bias (``score + 0.0 == score``
    bitwise), so a right-padded batch's valid positions score exactly what
    the unpadded batch would.
    """
    return jnp.where(kv_mask, 0.0, NEG_INF).astype(jnp.float32)


def _mask_bias(
    q_pos: Array, kv_pos: Array, window: int, causal: bool, protected: int = 0
) -> Array:
    """(Sq, Sk) additive bias from absolute positions."""
    q = q_pos[:, None]
    k = kv_pos[None, :]
    valid = k >= 0
    if causal:
        valid &= k <= q
    if window > 0:
        in_window = k > q - window
        if protected > 0:  # attention sinks are always visible
            in_window |= k < protected
        valid &= in_window
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def _softcap(x: Array, cap: float) -> Array:
    if cap > 0.0:
        return cap * jnp.tanh(x / cap)
    return x


# ---------------------------------------------------------------------------
# SDPA implementations. q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd)
# ---------------------------------------------------------------------------


def _naive_sdpa(
    q, k, v, q_pos, kv_pos, *, window, causal, softcap, protected=0,
    kv_mask=None,
):
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = _softcap(scores * (hd**-0.5), softcap)
    scores = scores + _mask_bias(q_pos, kv_pos, window, causal, protected)
    if kv_mask is not None:  # per-row pad-key mask (mixed-seq-len batches)
        scores = scores + _kv_mask_bias(kv_mask)[:, None, None, None, :]
    w = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (e.g. an all-pad row) -> zeros, matching the Pallas
    # kernel and the ref oracle, instead of softmax-of-garbage
    any_valid = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    w = jnp.where(any_valid, w, 0.0)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


def _chunked_sdpa(
    q, k, v, q_pos, kv_pos, *, window, causal, softcap, chunk, protected=0,
    kv_mask=None,
):
    """Streaming-softmax attention, scanned over KV chunks."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    nchunks = -(-sk // chunk)
    pad = nchunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
        if kv_mask is not None:
            kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))
    kc = k.reshape(b, nchunks, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(nchunks, chunk)
    mc = (
        None
        if kv_mask is None
        else kv_mask.reshape(b, nchunks, chunk).transpose(1, 0, 2)
    )

    qg = (q * (hd**-0.5)).reshape(b, sq, kv, g, hd)
    acc0 = jnp.zeros((b, sq, kv, g, hd), jnp.float32)
    m0 = jnp.full((b, sq, kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kv, g), jnp.float32)

    def body(carry, xs):
        acc, m, l = carry
        if mc is None:
            kj, vj, pj = xs
        else:
            kj, vj, pj, mj = xs
        s = jnp.einsum("bqkgd,bskd->bqkgs", qg, kj).astype(jnp.float32)
        s = _softcap(s, softcap)
        bias = _mask_bias(q_pos, pj, window, causal, protected)  # (sq, chunk)
        s = s + bias[None, :, None, None, :]
        if mc is not None:  # per-row pad-key mask (mixed-seq-len batches)
            s = s + _kv_mask_bias(mj)[:, None, None, None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard both exps below the mask floor so fully-masked rows keep
        # (acc, l) at exact zero and finalize to zeros (kernel semantics)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
        scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        acc = acc * scale[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", p.astype(vj.dtype), vj
        ).astype(jnp.float32)
        l = l * scale + jnp.sum(p, axis=-1)
        return (acc, m_new, l), None

    xs = (kc, vc, pc) if mc is None else (kc, vc, pc, mc)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), xs)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _banded_sdpa(
    q, k, v, q_pos, kv_pos, *, window, softcap, chunk, protected, kv_mask=None
):
    """Sliding-window attention that only touches in-band KV blocks.

    §Perf optimization: the plain chunked path computes every (q, kv) block
    and masks — at 32k tokens with a 4k window that is 8x wasted FLOPs and
    score memory.  Here q is cut into window-sized blocks; block i attends
    to kv blocks {i-1, i} (which cover the whole (q-W, q] band), plus the
    protected attention-sink prefix.  Requires aligned full-sequence layout
    (q_pos == kv_pos == arange(S)), which is how train/prefill call it.
    ``kv_mask`` (per-row pad-key mask) is sliced along the same band so
    right-padded mixed-seq-len batches stay on this fast path.
    """
    b, sq, h, hd = q.shape
    w = window
    nblocks = -(-sq // w)
    pad = nblocks * w - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-(10**9))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-1)
        if kv_mask is not None:
            kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * w, w, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * w, w, axis=0)
        lo = jnp.maximum(i - 1, 0) * w
        ks = jax.lax.dynamic_slice_in_dim(k, lo, 2 * w, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, lo, 2 * w, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(kv_pos, lo, 2 * w, axis=0)
        km = (
            None
            if kv_mask is None
            else jax.lax.dynamic_slice_in_dim(kv_mask, lo, 2 * w, axis=1)
        )
        if protected:
            # invalidate sink positions inside the band slice (early blocks
            # already cover them) before prepending the dedicated sink copy
            kp = jnp.where(kp < protected, -1, kp)
            ks = jnp.concatenate([k[:, :protected], ks], axis=1)
            vs = jnp.concatenate([v[:, :protected], vs], axis=1)
            kp = jnp.concatenate([kv_pos[:protected], kp], axis=0)
            if km is not None:
                km = jnp.concatenate([kv_mask[:, :protected], km], axis=1)
        return _chunked_sdpa(
            qs, ks, vs, qp, kp,
            window=window, causal=True, softcap=softcap,
            chunk=min(chunk, 2 * w), protected=protected, kv_mask=km,
        )

    outs = [block(jnp.int32(i)) for i in range(nblocks)] if nblocks <= 4 else None
    if outs is not None:
        out = jnp.concatenate(outs, axis=1)
    else:
        out = jax.lax.map(block, jnp.arange(nblocks)).transpose(1, 0, 2, 3, 4)
        out = out.reshape(b, nblocks * w, h, hd)
    return out[:, :sq]


def sdpa(
    q: Array,
    k: Array,
    v: Array,
    q_pos: Array,
    kv_pos: Array,
    *,
    window: int = 0,
    causal: bool = True,
    softcap: float = 0.0,
    impl: str = "auto",
    chunk: int = 1024,
    protected: int = 0,
    kv_mask: Array | None = None,
) -> Array:
    """``kv_mask`` is an optional (B, Sk) per-row key-validity mask — the
    mixed-seq-len serving path marks right-padding pad positions invalid so
    they get zero attention weight.  Every impl takes it natively: the
    Pallas flash kernel folds it into per-row key positions and the banded
    fast path slices it along the band, so masked mixed-length batches run
    the same fast kernels as unmasked ones.  The only remaining rewrite is
    an explicitly requested ``banded`` whose layout preconditions (causal,
    windowed, aligned full-sequence) don't hold — that goes through
    :func:`_fallback_to_chunked`, which warns once and notifies the
    fallback observers (``sampler_masked_fallback_total``)."""
    sq, sk = q.shape[1], k.shape[1]
    if (
        impl in ("auto", "chunked", "banded")
        and causal
        and window > 0
        and sq == sk
        and sq >= 4 * window
    ):
        return _banded_sdpa(
            q, k, v, q_pos, kv_pos,
            window=window, softcap=softcap, chunk=chunk, protected=protected,
            kv_mask=kv_mask,
        )
    if impl == "banded":
        # layout preconditions unmet (non-causal, unwindowed, or sq != sk)
        impl = _fallback_to_chunked("banded", "banded-layout-unmet")
    if impl == "auto":
        impl = "naive" if sq * sk <= 1024 * 2048 else "chunked"
    if impl == "naive":
        return _naive_sdpa(
            q, k, v, q_pos, kv_pos,
            window=window, causal=causal, softcap=softcap, protected=protected,
            kv_mask=kv_mask,
        )
    if impl == "chunked":
        return _chunked_sdpa(
            q, k, v, q_pos, kv_pos,
            window=window, causal=causal, softcap=softcap,
            chunk=min(chunk, max(sk, 128)), protected=protected,
            kv_mask=kv_mask,
        )
    if impl == "pallas":
        opts = (window, causal, softcap, protected, min(chunk, max(sk, 128)))
        return _pallas_sdpa(q, k, v, q_pos, kv_pos, kv_mask, opts)
    raise ValueError(f"unknown attention impl {impl!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _pallas_sdpa(q, k, v, q_pos, kv_pos, kv_mask, opts):
    """The Pallas flash kernel.  It has no backward pass of its own, so its
    gradient is the chunked XLA path's, which computes the same function:
    ``auto`` picks this kernel on TPU for training as well as sampling."""
    from repro.kernels import ops as kops

    window, causal, softcap, protected, _ = opts
    return kops.flash_attention(
        q, k, v, q_pos, kv_pos,
        window=window, causal=causal, softcap=softcap,
        protected=protected, kv_mask=kv_mask,
    )


def _pallas_sdpa_fwd(q, k, v, q_pos, kv_pos, kv_mask, opts):
    out = _pallas_sdpa(q, k, v, q_pos, kv_pos, kv_mask, opts)
    return out, (q, k, v, q_pos, kv_pos, kv_mask)


def _pallas_sdpa_bwd(opts, res, g):
    q, k, v, q_pos, kv_pos, kv_mask = res
    window, causal, softcap, protected, chunk = opts
    _, vjp = jax.vjp(
        lambda q, k, v: _chunked_sdpa(
            q, k, v, q_pos, kv_pos,
            window=window, causal=causal, softcap=softcap, chunk=chunk,
            protected=protected, kv_mask=kv_mask,
        ),
        q, k, v,
    )
    # positions and the mask are not differentiable
    return (*vjp(g), None, None, None)


_pallas_sdpa.defvjp(_pallas_sdpa_fwd, _pallas_sdpa_bwd)


# ---------------------------------------------------------------------------
# KV cache (per layer). Slots carry absolute positions; -1 = empty.
# Ring buffers (slots < max_position) implement sliding-window decode.
# ---------------------------------------------------------------------------


def cache_specs(batch: int, slots: int, kv_heads: int, head_dim: int) -> dict:
    return {
        "k": L.P((batch, slots, kv_heads, head_dim), "zeros"),
        "v": L.P((batch, slots, kv_heads, head_dim), "zeros"),
        "pos": L.P((slots,), "zeros"),  # stored as int32 via init_cache
    }


def init_cache(
    batch: int, slots: int, kv_heads: int, head_dim: int, dtype,
    quant: bool = False,
):
    if quant:  # int8 entries + per-(slot, head) scales (§Perf: decode is
        # memory-bound on cache streaming; int8 halves the bytes)
        return {
            "k": jnp.zeros((batch, slots, kv_heads, head_dim), jnp.int8),
            "v": jnp.zeros((batch, slots, kv_heads, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, slots, kv_heads, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, slots, kv_heads, 1), jnp.float32),
            "pos": jnp.full((slots,), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, slots, kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, slots, kv_heads, head_dim), dtype),
        "pos": jnp.full((slots,), -1, jnp.int32),
    }


def abstract_cache(
    batch: int, slots: int, kv_heads: int, head_dim: int, dtype,
    quant: bool = False,
):
    """ShapeDtypeStruct mirror of init_cache (no allocation)."""
    if quant:
        return {
            "k": jax.ShapeDtypeStruct((batch, slots, kv_heads, head_dim), jnp.int8),
            "v": jax.ShapeDtypeStruct((batch, slots, kv_heads, head_dim), jnp.int8),
            "k_scale": jax.ShapeDtypeStruct((batch, slots, kv_heads, 1), jnp.float32),
            "v_scale": jax.ShapeDtypeStruct((batch, slots, kv_heads, 1), jnp.float32),
            "pos": jax.ShapeDtypeStruct((slots,), jnp.int32),
        }
    return {
        "k": jax.ShapeDtypeStruct((batch, slots, kv_heads, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, slots, kv_heads, head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((slots,), jnp.int32),
    }


def _quantize(x: Array) -> tuple[Array, Array]:
    """Per-(token, head) symmetric int8. x: (B, S, KV, hd)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def cache_kv(cache: dict, dtype) -> tuple[Array, Array]:
    """Read K/V from a (possibly quantized) cache."""
    if cache["k"].dtype == jnp.int8:
        return (
            _dequant(cache["k"], cache["k_scale"], dtype),
            _dequant(cache["v"], cache["v_scale"], dtype),
        )
    return cache["k"], cache["v"]


def cache_insert(cache: dict, k: Array, v: Array, pos: Array, protected: int = 0) -> dict:
    """Insert one step (S=1) at absolute position `pos` (scalar).

    ``protected`` reserves the first slots for never-evicted prefix tokens
    (attention sinks / Hymba meta tokens) when the cache is a ring buffer.
    """
    slots = cache["k"].shape[1]
    if protected > 0 and protected < slots:
        ring = slots - protected
        slot = jnp.where(
            pos < protected, pos, protected + (pos - protected) % ring
        )
    else:
        slot = pos % slots
    out = dict(cache)
    if cache["k"].dtype == jnp.int8:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        out["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, axis=1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, axis=1)
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, slot, axis=1
        )
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, slot, axis=1
        )
    else:
        out["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1
        )
        out["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1
        )
    out["pos"] = jax.lax.dynamic_update_index_in_dim(
        cache["pos"], pos.astype(jnp.int32), slot, axis=0
    )
    return out


def cache_fill(cache: dict, k: Array, v: Array, start: Array) -> dict:
    """Prefill: write S consecutive steps starting at `start` (ring-aware
    only for start=0 and S<=slots; prefill always satisfies this)."""
    s = k.shape[1]
    slots = cache["k"].shape[1]
    pos = start + jnp.arange(s, dtype=jnp.int32)
    if s > slots:
        # keep only the last `slots` entries (window prefill)
        k, v, pos = k[:, -slots:], v[:, -slots:], pos[-slots:]
        s = slots
    out = dict(cache)
    if cache["k"].dtype == jnp.int8:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        out["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, start % slots, axis=1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, start % slots, axis=1)
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, start % slots, axis=1
        )
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, start % slots, axis=1
        )
    else:
        out["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), start % slots, axis=1
        )
        out["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), start % slots, axis=1
        )
    out["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos, start % slots, axis=0
    )
    return out


# ---------------------------------------------------------------------------
# Full GQA attention layer (projections + rope + cache + sdpa)
# ---------------------------------------------------------------------------


def attention_specs(cfg) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": L.linear_specs(d, h * hd, bias=cfg.qkv_bias),
        "wk": L.linear_specs(d, kv * hd, bias=cfg.qkv_bias),
        "wv": L.linear_specs(d, kv * hd, bias=cfg.qkv_bias),
        "wo": L.linear_specs(h * hd, d),
    }


def attention(
    p: dict,
    x: Array,
    cfg,
    *,
    mode: str,
    cache: dict | None = None,
    pos: Array | None = None,
    window: int = 0,
    causal: bool = True,
    cross_kv: tuple[Array, Array] | None = None,
    protected: int = 0,
    lengths: Array | None = None,
) -> tuple[Array, dict | None]:
    """mode: 'train' | 'prefill' | 'decode'. Returns (out, new_cache).

    ``lengths`` ((B,) int32, train-mode full-sequence layout only) marks
    positions >= lengths[b] as right-padding: those keys are masked out of
    every row's softmax, so a padded batch's valid positions attend to
    exactly the keys an unpadded batch would."""
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim

    q = L.linear(p["wq"], x).reshape(b, s, h, hd)

    if cross_kv is not None:
        # cross attention (Whisper decoder): kv from encoder, no cache mgmt
        ek, ev = cross_kv
        q_pos = jnp.zeros((s,), jnp.int32) if pos is None else (
            pos + jnp.arange(s, dtype=jnp.int32)
        )
        kv_pos = jnp.arange(ek.shape[1], dtype=jnp.int32)
        out = sdpa(
            q, ek, ev, q_pos, kv_pos,
            window=0, causal=False, softcap=cfg.attn_logit_softcap,
            impl=resolve_impl(cfg, s, ek.shape[1]), chunk=cfg.attn_chunk,
        )
        return L.linear(p["wo"], out.reshape(b, s, h * hd)), cache

    k = L.linear(p["wk"], x).reshape(b, s, kvh, hd)
    v = L.linear(p["wv"], x).reshape(b, s, kvh, hd)

    if mode in ("train", "prefill"):
        positions = jnp.arange(s, dtype=jnp.int32)
    else:  # decode: single token at absolute position `pos`
        positions = jnp.atleast_1d(jnp.asarray(pos, jnp.int32))

    if cfg.use_rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    new_cache = cache
    if mode == "decode":
        assert cache is not None
        new_cache = cache_insert(cache, k, v, positions[0], protected)
        k_all, v_all = cache_kv(new_cache, k.dtype)
        kv_pos = new_cache["pos"]
        out = sdpa(
            q, k_all, v_all, positions, kv_pos,
            window=window, causal=True, softcap=cfg.attn_logit_softcap,
            impl=resolve_impl(cfg, 1, k_all.shape[1]), chunk=cfg.attn_chunk,
            protected=protected,
        )
    else:
        if mode == "prefill" and cache is not None:
            new_cache = cache_fill(cache, k, v, jnp.int32(0))
        kv_mask = (
            None
            if lengths is None
            else jnp.arange(s, dtype=jnp.int32) < lengths[:, None]
        )
        out = sdpa(
            q, k, v, positions, positions,
            window=window, causal=causal, softcap=cfg.attn_logit_softcap,
            impl=resolve_impl(cfg, s, s), chunk=cfg.attn_chunk,
            protected=protected, kv_mask=kv_mask,
        )

    return L.linear(p["wo"], out.reshape(b, s, h * hd)), new_cache


def resolve_impl(cfg, sq: int, sk: int) -> str:
    """The SDPA impl a layer runs: ``cfg.attention_impl`` unless it is
    ``auto``, which picks by platform.  On TPU every multi-query call runs
    the Pallas flash kernel (it pads any head_dim and sequence length to its
    blocks); single-token decode and every other platform run XLA (naive
    for short sequences, chunked above)."""
    if cfg.attention_impl != "auto":
        return cfg.attention_impl
    if sq > 1 and jax.default_backend() == "tpu":
        return "pallas"
    return "naive" if sq * sk <= 1024 * 2048 else "chunked"
