"""State-space / recurrent blocks: Mamba (Hymba heads) and xLSTM cells.

TPU adaptation notes (DESIGN.md §4): on TPU a Mamba sequence (S > 1) runs
the Pallas kernel :mod:`repro.kernels.selective_scan`, which, like Mamba's
CUDA "selective scan", keeps the (d_inner, N) state on chip and sweeps it
over time, reading dt, x, B, C and writing y once.  Decode (S = 1) and
every other platform run :func:`chunked_ssm_outputs`, a *chunked*
linear-recurrence scan — ``lax.scan`` over sequence chunks with an
associative scan inside each chunk — which keeps the live state tensor at
(B, chunk, d_inner, N) instead of (B, S, d_inner, N); it is also the
kernel's backward pass and the reference it is tested against.
xLSTM's sLSTM is an inherently sequential recurrence (recurrent weights),
implemented as a time scan; mLSTM (matrix memory) uses the same chunked
pattern as Mamba.

Right-pad prefix-safety (the mixed-seq-len masking contract): every scan
in this module is strictly left-to-right — ``causal_conv1d`` left-pads,
the chunked recurrences carry state forward only, and the intra-chunk
mLSTM scores are tril-masked to exact zeros before any contraction — so a
right-padded row's outputs at positions ``< length`` are identical to the
exact-shape run's.  The Pallas kernel has it by construction: each step
reads only its own position and the carried state, and a pad step
(``dt = 0``) leaves the state as it is.  Two structural facts make the
chunked scan's identity *bitwise*, not just mathematical: (1)
``jax.lax.associative_scan``'s combine tree for prefix element ``p``
depends only on ``p`` (Brent–Kung interleave), not on the scanned length,
so a longer padded axis doesn't re-associate prefix sums; (2) chunk
boundaries inside the prefix coincide between the exact and padded runs
(``chunk = min(chunk, s)`` either yields the same chunking over the
prefix, or both runs put the whole prefix in their first chunk), and
masked/pad slots contribute exact ``+0.0`` terms to the fixed-shape
contractions.  mLSTM contracts over the whole chunk, so its chunk length is
fixed (a short sequence pads up to it) instead of ``min(chunk, s)``: a sum
over 5 terms and one over 9 with 4 exact zeros can round differently.
``tests/test_prefix_safety.py`` walls this per block kind;
it is what lets SSM kinds join ``MASKABLE_BLOCKS`` in
:mod:`repro.models.diffusion`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L

Array = jax.Array


# ---------------------------------------------------------------------------
# Linear recurrence helpers:  h_t = a_t * h_{t-1} + b_t   (associative)
# ---------------------------------------------------------------------------


def _assoc_op(l, r):
    al, bl = l
    ar, br = r
    return al * ar, br + ar * bl


def chunked_linear_scan(a: Array, b: Array, h0: Array, chunk: int):
    """Scan h_t = a_t h_{t-1} + b_t over axis 1 (time).

    a: (B, S, ...) gate — trailing dims may be 1 (broadcast against b).
    b: (B, S, ...);  h0: (B, ...) matching b's trailing dims.
    Returns (h_all (B, S, ...), h_last).
    """
    bsz, s = b.shape[0], b.shape[1]
    chunk = min(chunk, s)
    nchunks = -(-s // chunk)
    pad = nchunks * chunk - s
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad)) + ((0, 0),) * (b.ndim - 2))
    ac = jnp.moveaxis(a.reshape((bsz, nchunks, chunk) + a.shape[2:]), 1, 0)
    bc = jnp.moveaxis(b.reshape((bsz, nchunks, chunk) + b.shape[2:]), 1, 0)

    def body(h, xs):
        aj, bj = xs                                  # (B, chunk, ...)
        # fold carry into the first step of the chunk
        bj = bj.at[:, 0].add(aj[:, 0] * h)
        _, hh = jax.lax.associative_scan(_assoc_op, (aj, bj), axis=1)
        return hh[:, -1], hh

    h_last, hs = jax.lax.scan(body, h0, (ac, bc))
    hs = jnp.moveaxis(hs, 0, 1).reshape((bsz, nchunks * chunk) + b.shape[2:])
    return hs[:, :s], h_last


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — used by Hymba's SSM heads
# ---------------------------------------------------------------------------


def chunked_ssm_outputs(
    dt32: Array, x32: Array, a: Array, bmat: Array, c: Array,
    h0: Array, chunk: int,
):
    """Fused selective scan: discretize + recur + read out, per chunk.

    §Perf: materializing the discretized (B, S, d_inner, N) tensors (a_bar,
    dt*B*x) before the scan dominated Hymba train memory (98 GB/device).
    Here BOTH the discretization and the <c_t, h_t> readout happen inside
    each chunk body, so only (B, chunk, d_inner, N) tensors ever exist.

    dt32, x32: (B, S, d); a: (d, N); bmat, c: (B, S, N); h0: (B, d, N).
    Returns (y (B, S, d), h_last).
    """
    bsz, s = x32.shape[0], x32.shape[1]
    chunk = min(chunk, s)
    nchunks = -(-s // chunk)
    pad = nchunks * chunk - s
    if pad:
        p2 = ((0, 0), (0, pad), (0, 0))
        dt32 = jnp.pad(dt32, p2)  # dt=0 => a_bar=1, bx=0: identity steps
        x32 = jnp.pad(x32, p2)
        bmat = jnp.pad(bmat, p2)
        c = jnp.pad(c, p2)

    def to_chunks(t):
        return jnp.moveaxis(t.reshape((bsz, nchunks, chunk) + t.shape[2:]), 1, 0)

    @jax.checkpoint  # per-chunk remat: bwd recomputes the (B,L,d,N)
    def body(h, xs):  # intermediates chunk-by-chunk instead of saving all
        dtj, xj, bj, cj = xs                          # (B, L, *) small
        a_bar = jnp.exp(dtj[..., None] * a)           # (B, L, d, N)
        bx = (dtj * xj)[..., None] * bj[..., None, :]
        bx = bx.at[:, 0].add(a_bar[:, 0] * h)
        _, hh = jax.lax.associative_scan(_assoc_op, (a_bar, bx), axis=1)
        y = jnp.einsum("bsdn,bsn->bsd", hh, cj)
        return hh[:, -1], y

    h_last, ys = jax.lax.scan(
        body, h0, (to_chunks(dt32), to_chunks(x32), to_chunks(bmat), to_chunks(c))
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, nchunks * chunk, -1)
    return y[:, :s], h_last


def _f32(*ts):
    return tuple(t.astype(jnp.float32) for t in ts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _pallas_scan(dt, x, a, bmat, c, h0, chunk):
    """The Pallas selective scan on operands in their compute dtype (cast
    to float32 on chip).  It has no backward pass of its own, so its
    gradient is :func:`chunked_ssm_outputs`', which computes the same
    function."""
    from repro.kernels import ops as kops

    return kops.selective_scan(dt, x, a, bmat, c, h0)


def _pallas_scan_fwd(dt, x, a, bmat, c, h0, chunk):
    return _pallas_scan(dt, x, a, bmat, c, h0, chunk), (dt, x, a, bmat, c, h0)


def _pallas_scan_bwd(chunk, res, g):
    _, vjp = jax.vjp(
        lambda *ts: chunked_ssm_outputs(*_f32(*ts), chunk), *res
    )
    return vjp(g)


_pallas_scan.defvjp(_pallas_scan_fwd, _pallas_scan_bwd)


def mamba_specs(cfg) -> dict:
    m = cfg.ssm
    d = cfg.d_model
    di = m.expand * d
    dtr = m.dt_rank or -(-d // 16)
    return {
        "in_proj": L.linear_specs(d, 2 * di),
        "conv": L.causal_conv_specs(di, m.conv_dim),
        "x_proj": L.linear_specs(di, dtr + 2 * m.state_dim),
        "dt_proj": L.linear_specs(dtr, di, bias=True),
        "A_log": L.P((di, m.state_dim), "normal", 0.5),
        "D": L.P((di,), "ones"),
        "out_proj": L.linear_specs(di, d),
    }


def _mamba_core(p, xz: Array, cfg, conv_state, ssm_state, *, chunk):
    """Shared seq/step Mamba math. xz: (B, S, 2*di)."""
    m = cfg.ssm
    di = m.expand * cfg.d_model
    dtr = m.dt_rank or -(-cfg.d_model // 16)
    x, z = jnp.split(xz, 2, axis=-1)
    x, conv_state = L.causal_conv1d(p["conv"], x, conv_state)
    x = jax.nn.silu(x)

    proj = L.linear(p["x_proj"], x)                    # (B,S,dtr+2N)
    dt, bmat, cmat = jnp.split(proj, [dtr, dtr + m.state_dim], axis=-1)
    dt = jax.nn.softplus(L.linear(p["dt_proj"], dt))   # (B,S,di)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))       # (di,N)

    # discretization (a_bar = exp(dt*A), b_bar = dt*B*x), recurrence and the
    # <c, h> readout in one pass: no (B, S, d_inner, N) tensor is ever
    # materialized.  On TPU a sequence runs the Pallas kernel; decode and
    # every other platform run the chunked scan.
    with jax.named_scope("mamba.scan"):
        if xz.shape[1] > 1 and jax.default_backend() == "tpu":
            y, h_last = _pallas_scan(dt, x, a, bmat, cmat, ssm_state, chunk)
        else:
            y, h_last = chunked_ssm_outputs(
                *_f32(dt, x, a, bmat, cmat, ssm_state), chunk
            )
    y = (y + x.astype(jnp.float32) * p["D"].astype(jnp.float32)).astype(x.dtype)
    y = y * jax.nn.silu(z)
    return y, conv_state, h_last


def mamba(p, x: Array, cfg, state: dict | None = None, mode: str = "train"):
    """x: (B, S, d). state: {"conv": (B,W-1,di), "ssm": (B,di,N)} or None."""
    m = cfg.ssm
    b = x.shape[0]
    di = m.expand * cfg.d_model
    if state is None:
        conv_state = None
        ssm_state = jnp.zeros((b, di, m.state_dim), jnp.float32)
    else:
        conv_state, ssm_state = state["conv"], state["ssm"]
    xz = L.linear(p["in_proj"], x)
    y, conv_state, ssm_state = _mamba_core(
        p, xz, cfg, conv_state, ssm_state, chunk=m.chunk
    )
    out = L.linear(p["out_proj"], y)
    new_state = {"conv": conv_state, "ssm": ssm_state}
    return out, new_state


def mamba_init_state(cfg, batch: int, dtype):
    m = cfg.ssm
    di = m.expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, m.conv_dim - 1, di), dtype),
        "ssm": jnp.zeros((batch, di, m.state_dim), jnp.float32),
    }


def mamba_abstract_state(cfg, batch: int, dtype):
    m = cfg.ssm
    di = m.expand * cfg.d_model
    return {
        "conv": jax.ShapeDtypeStruct((batch, m.conv_dim - 1, di), dtype),
        "ssm": jax.ShapeDtypeStruct((batch, di, m.state_dim), jnp.float32),
    }


# ---------------------------------------------------------------------------
# xLSTM: mLSTM (matrix memory, chunk-parallelizable) and sLSTM (scalar
# memory with recurrent weights, sequential) — arXiv:2405.04517
# ---------------------------------------------------------------------------


def mlstm_zero_state(b: int, nh: int, hd: int) -> dict:
    return {
        "c": jnp.zeros((b, nh, hd, hd), jnp.float32),
        "n": jnp.zeros((b, nh, hd), jnp.float32),
        "m": jnp.full((b, nh), -1e30, jnp.float32),
    }


def mlstm_chunkwise(q, k, v, i_pre, logf, state, chunk: int):
    """Chunkwise-parallel stabilized mLSTM (matrix memory).

    q,k,v: (B,S,nh,hd); i_pre/logf: (B,S,nh) log-domain gates.
    state: {"c": (B,nh,hd,hd), "n": (B,nh,hd), "m": (B,nh)} where c,n are
    stored *stabilized* (true C = c * exp(m)).

    The TPU-native form (DESIGN.md §4): per chunk, the output splits into an
    inter-chunk term (decayed boundary state) and an intra-chunk term
    (attention-like (L,L) matmul), so per-step (hd,hd) outer products are
    never materialized along the sequence.
    """
    b, s, nh, hd = q.shape
    # the chunk length never follows s: a short sequence pads up to it, so
    # a prefix's intra-chunk sums have the same length (hence the same
    # float association) however far the sequence is right-padded
    chunk = max(chunk, 1)
    nchunks = -(-s // chunk)
    pad = nchunks * chunk - s
    if pad:
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v = zpad(q), zpad(k), zpad(v)
        i_pre = jnp.pad(
            i_pre, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30
        )
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))

    def to_chunks(t):
        return jnp.moveaxis(
            t.reshape((b, nchunks, chunk) + t.shape[2:]), 1, 0
        )

    qc, kc, vc = to_chunks(q.astype(jnp.float32)), to_chunks(k.astype(jnp.float32)), to_chunks(v.astype(jnp.float32))
    ic, fc = to_chunks(i_pre), to_chunks(logf)

    def body(carry, xs):
        c0, n0, m0 = carry                       # stabilized: C = c0 e^{m0}
        qj, kj, vj, ij, fj, rj = xs              # (B,L,nh,*); rj (L,) real
        cum = jnp.cumsum(fj, axis=1)             # (B,L,nh): sum_{u<=j} logf_u
        # running max of (logi_i - cum_i) over i<=j
        g = jax.lax.associative_scan(jnp.maximum, ij - cum, axis=1)
        m_all = cum + jnp.maximum(m0[:, None], g)           # (B,L,nh)
        # inter-chunk: exp(cum_j + m0 - m_j) * q_j C_0
        inter_w = jnp.exp(cum + m0[:, None] - m_all)        # (B,L,nh)
        h_inter = jnp.einsum("blnd,bnde->blne", qj, c0) * inter_w[..., None]
        n_inter = n0[:, None] * inter_w[..., None]          # (B,L,nh,hd)
        # intra-chunk: scores[j,i] = exp(cum_j - cum_i + logi_i - m_j) q_j.k_i
        logw = (
            cum[:, :, None] - cum[:, None, :] + ij[:, None, :]
            - m_all[:, :, None]
        )                                                   # (B,Lq,Lk,nh)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        # mask BEFORE exp: future-position logw can overflow, and
        # where(mask, exp(inf), 0) still propagates NaN gradients
        logw = jnp.where(mask[None, :, :, None], logw, -1e30)
        w_intra = jnp.exp(jnp.minimum(logw, 60.0))
        scores = jnp.einsum("blnd,bind->blin", qj, kj) * w_intra
        h_intra = jnp.einsum("blin,bind->blnd", scores, vj)
        n_intra = jnp.einsum("blin,bind->blnd", w_intra, kj)
        num = h_inter + h_intra
        n_all = n_inter + n_intra
        den = jnp.maximum(
            jnp.abs(jnp.einsum("blnd,blnd->bln", n_all, qj)), jnp.exp(-m_all)
        )
        # pad rows (zero queries) are dropped, but a tiny den there would
        # put 0/0 into the gradient of the division
        den = jnp.where(rj[None, :, None], den, 1.0)
        h = num / den[..., None]
        # carry update (stabilized at m_last)
        m_last = m_all[:, -1]
        cum_l = cum[:, -1]                                   # (B,nh)
        wc = jnp.exp(cum_l + m0 - m_last)
        wi = jnp.exp(cum_l[:, None] - cum + ij - m_last[:, None])  # (B,L,nh)
        c_new = c0 * wc[..., None, None] + jnp.einsum(
            "blnd,blne->bnde", kj * wi[..., None], vj
        )
        n_new = n0 * wc[..., None] + jnp.einsum("blnd,bln->bnd", kj, wi)
        return (c_new, n_new, m_last), h

    real = (jnp.arange(nchunks * chunk) < s).reshape(nchunks, chunk)
    (c, n, m), hs = jax.lax.scan(
        body, (state["c"], state["n"], state["m"]), (qc, kc, vc, ic, fc, real)
    )
    h = jnp.moveaxis(hs, 0, 1).reshape(b, nchunks * chunk, nh, hd)[:, :s]
    return h, {"c": c, "n": n, "m": m}


def mlstm_step(q, k, v, i_pre, logf, state):
    """Single-token recurrent mLSTM update (decode). q/k/v: (B,1,nh,hd)."""
    qj, kj, vj = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
    ip, lf = i_pre[:, 0], logf[:, 0]                     # (B,nh)
    c0, n0, m0 = state["c"], state["n"], state["m"]
    m_new = jnp.maximum(lf + m0, ip)
    fg = jnp.exp(lf + m0 - m_new)[..., None]
    ig = jnp.exp(ip - m_new)[..., None]
    c = c0 * fg[..., None] + (ig * kj)[..., :, None] * vj[..., None, :]
    n = n0 * fg + ig * kj
    den = jnp.maximum(jnp.abs(jnp.sum(n * qj, -1)), jnp.exp(-m_new))
    h = jnp.einsum("bnde,bnd->bne", c, qj) / den[..., None]
    return h[:, None], {"c": c, "n": n, "m": m_new}


def mlstm_specs(cfg) -> dict:
    d = cfg.d_model
    nh = cfg.num_heads
    di = 2 * d                       # up-projection factor 2
    return {
        "norm": L.rmsnorm_specs(d),
        "up": L.linear_specs(d, 2 * di),
        "conv": L.causal_conv_specs(di, 4),
        "wq": L.linear_specs(di, di),
        "wk": L.linear_specs(di, di),
        "wv": L.linear_specs(di, di),
        "wi": L.linear_specs(di, nh, bias=True),
        "wf": L.linear_specs(di, nh, bias=True),
        "out_norm": L.rmsnorm_specs(di),
        "down": L.linear_specs(di, d),
    }


def mlstm_block(p, x: Array, cfg, state=None, mode: str = "train"):
    """Pre-norm residual mLSTM block. x: (B,S,d)."""
    d = cfg.d_model
    nh = cfg.num_heads
    di = 2 * d
    hd = di // nh
    b, s, _ = x.shape
    chunk = (cfg.ssm.chunk if cfg.ssm else 256)

    h = L.rmsnorm(p["norm"], x, cfg.norm_eps)
    up = L.linear(p["up"], h)
    xm, z = jnp.split(up, 2, axis=-1)
    conv_state = None if state is None else state["conv"]
    xc, conv_state = L.causal_conv1d(p["conv"], xm, conv_state)
    xc = jax.nn.silu(xc)

    q = L.linear(p["wq"], xc).reshape(b, s, nh, hd)
    k = L.linear(p["wk"], xc).reshape(b, s, nh, hd) * (hd**-0.5)
    v = L.linear(p["wv"], xm).reshape(b, s, nh, hd)
    # exponential gating with log-domain stabilization
    i_pre = L.linear(p["wi"], xc).astype(jnp.float32)      # (B,S,nh)
    f_pre = L.linear(p["wf"], xc).astype(jnp.float32)

    logf = -jax.nn.softplus(-f_pre)                        # log sigmoid(f_pre)
    if state is None:
        mstate = mlstm_zero_state(b, nh, hd)
    else:
        mstate = {k_: state[k_] for k_ in ("c", "n", "m")}
    if mode == "decode":
        hout, mstate = mlstm_step(q, k, v, i_pre, logf, mstate)
    else:
        hout, mstate = mlstm_chunkwise(q, k, v, i_pre, logf, mstate, chunk)
    c_last, n_last, m_last = mstate["c"], mstate["n"], mstate["m"]
    hout = hout.reshape(b, s, di).astype(x.dtype)
    hout = L.rmsnorm(p["out_norm"], hout, cfg.norm_eps)
    out = L.linear(p["down"], hout * jax.nn.silu(z))
    new_state = {"conv": conv_state, "c": c_last, "n": n_last, "m": m_last}
    return x + out, new_state


def mlstm_init_state(cfg, batch: int, dtype):
    d, nh = cfg.d_model, cfg.num_heads
    di = 2 * d
    hd = di // nh
    return dict(
        conv=jnp.zeros((batch, 3, di), dtype), **mlstm_zero_state(batch, nh, hd)
    )


def slstm_specs(cfg) -> dict:
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    return {
        "norm": L.rmsnorm_specs(d),
        "wz": L.linear_specs(d, d, bias=True),
        "wi": L.linear_specs(d, d, bias=True),
        "wf": L.linear_specs(d, d, bias=True),
        "wo": L.linear_specs(d, d, bias=True),
        # block-diagonal recurrent weights, one (hd, hd) block per head
        "rz": L.P((nh, hd, hd), "normal", 0.02),
        "ri": L.P((nh, hd, hd), "normal", 0.02),
        "rf": L.P((nh, hd, hd), "normal", 0.02),
        "ro": L.P((nh, hd, hd), "normal", 0.02),
        "out_norm": L.rmsnorm_specs(d),
        "down": L.linear_specs(d, d),
    }


def slstm_block(p, x: Array, cfg, state=None, mode: str = "train"):
    """sLSTM block: sequential time scan (recurrent weights). x: (B,S,d)."""
    d, nh = cfg.d_model, cfg.num_heads
    hd = d // nh
    b, s, _ = x.shape

    xn = L.rmsnorm(p["norm"], x, cfg.norm_eps)
    pre = {
        g: L.linear(p["w" + g], xn).astype(jnp.float32).reshape(b, s, nh, hd)
        for g in ("z", "i", "f", "o")
    }
    if state is None:
        h0 = jnp.zeros((b, nh, hd), jnp.float32)
        c0 = jnp.zeros((b, nh, hd), jnp.float32)
        n0 = jnp.ones((b, nh, hd), jnp.float32)
        m0 = jnp.zeros((b, nh, hd), jnp.float32)
    else:
        h0, c0, n0, m0 = state["h"], state["c"], state["n"], state["m"]

    rz = p["rz"].astype(jnp.float32)
    ri = p["ri"].astype(jnp.float32)
    rf = p["rf"].astype(jnp.float32)
    ro = p["ro"].astype(jnp.float32)

    def step(carry, xs):
        h, c, n, m = carry
        pz, pi, pf, po = xs
        rec = lambda r: jnp.einsum("bnj,nij->bni", h, r)
        z = jnp.tanh(pz + rec(rz))
        i_pre = pi + rec(ri)
        f_pre = pf + rec(rf)
        o = jax.nn.sigmoid(po + rec(ro))
        logf = -jax.nn.softplus(-f_pre)
        m_new = jnp.maximum(logf + m, i_pre)
        i_g = jnp.exp(i_pre - m_new)
        f_g = jnp.exp(logf + m - m_new)
        c = f_g * c + i_g * z
        n = f_g * n + i_g
        h = o * c / jnp.maximum(n, 1.0)
        return (h, c, n, m_new), h

    xs = tuple(jnp.moveaxis(pre[g], 1, 0) for g in ("z", "i", "f", "o"))
    (h_l, c_l, n_l, m_l), hs = jax.lax.scan(step, (h0, c0, n0, m0), xs)
    hout = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(x.dtype)
    hout = L.rmsnorm(p["out_norm"], hout, cfg.norm_eps)
    out = L.linear(p["down"], hout)
    new_state = {"h": h_l, "c": c_l, "n": n_l, "m": m_l}
    return x + out, new_state


def slstm_init_state(cfg, batch: int, dtype):
    d, nh = cfg.d_model, cfg.num_heads
    hd = d // nh
    z = lambda: jnp.zeros((batch, nh, hd), jnp.float32)
    return {"h": z(), "c": z(), "n": jnp.ones((batch, nh, hd), jnp.float32), "m": z()}
