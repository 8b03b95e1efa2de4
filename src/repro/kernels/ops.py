"""jit'd public wrappers around the Pallas kernels.

Handles the hardware-alignment plumbing so callers keep natural shapes:
* pads head_dim to a 128 multiple and seq lens to block multiples
  (padded key slots get position -1 => masked out; padded head dims are
  zeros => contribute nothing to dot products, scale uses the true hd),
  with the flash kernel's tiles picked from the call's shape;
* pads GQA group G to the f32 sublane multiple (8) for the decode kernel;
* lays out the selective scan's operands (A and the state transposed, d_inner
  on lanes) and pads its sequence to the time block with identity steps;
* picks how the kernels run from the platform: compiled by Mosaic on TPU,
  in interpret mode everywhere else, so the same call sites run in CPU
  tests and on the chip.  There is no fallback: a kernel that fails to
  lower or compile raises.

XLA cannot partition a Mosaic kernel, so on a multi-device mesh a caller
runs these per batch shard (``parallel.sharding.per_batch_shard``, as the
serving executor does); every kernel here is row-local.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import era_update as _era
from repro.kernels import flash_attention as _fa
from repro.kernels import selective_scan as _ss
from repro.core.lagrange import lagrange_weights

Array = jax.Array


def interpret_mode() -> bool:
    """True off TPU, where the Pallas kernels run in interpret mode."""
    return jax.default_backend() != "tpu"


def _pad_to(x: Array, mult: int, axis: int, value=0) -> Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


#: VMEM one step of the flash kernel may fill (v5e's default scoped limit is
#: 16 MiB)
FLASH_VMEM_BYTES = 12 << 20
#: the tallest q tile
FLASH_BLOCK_Q = 512


def _flash_vmem_bytes(bq: int, bk: int, hd: int, itemsize: int) -> int:
    """VMEM a (bq, bk) step of the flash kernel holds: the float32 scores
    and their exponentials, p in the operand dtype, the q, k, v and output
    blocks double-buffered, the float32 accumulator, and the position and
    softmax-state columns, which pad to 128 lanes ((bq, 1)) or 8 sublanes
    ((1, bk))."""
    scores = bq * bk * (2 * 4 + itemsize)
    blocks = 2 * (2 * bq + 2 * bk) * hd * itemsize
    columns = 4 * bq * 128 * 4 + 2 * 8 * bk * 4
    return scores + blocks + bq * hd * 4 + columns


def _flash_blocks(sq: int, sk: int, hd: int, itemsize: int) -> tuple[int, int]:
    """The flash kernel's (q tile, key block) for a call's shape.

    q tiles are as tall as ``FLASH_BLOCK_Q``, split evenly over ``sq`` and
    rounded up to 16 rows.  The key block is the whole key length, rounded
    up to 128, up to the widest power of two whose ``FLASH_BLOCK_Q``-tall
    tile fits ``FLASH_VMEM_BYTES``: where the keys fit one block, one head's
    K and V are fetched once, not once per q tile.  That widest block
    depends on ``hd`` and the dtype alone, so where keys take more than one
    block they split at the same multiples of it however far a row is
    padded: padding adds masked keys past a row's valid prefix to its last
    block, and fully masked blocks after it."""
    nq = -(-sq // FLASH_BLOCK_Q)
    bq = -(-sq // (16 * nq)) * 16
    widest = 128
    while (
        _flash_vmem_bytes(FLASH_BLOCK_Q, 2 * widest, hd, itemsize)
        <= FLASH_VMEM_BYTES
    ):
        widest *= 2
    return bq, min(-(-sk // 128) * 128, widest)


@functools.partial(
    jax.jit, static_argnames=("window", "causal", "softcap", "protected")
)
def flash_attention(
    q: Array,       # (B, Sq, H, hd) — model layout
    k: Array,       # (B, Sk, KV, hd)
    v: Array,
    q_pos: Array,
    kv_pos: Array,
    *,
    kv_mask: Array | None = None,  # (B, Sk) bool/int, nonzero = valid key
    window: int = 0,
    causal: bool = True,
    softcap: float = 0.0,
    protected: int = 0,
) -> Array:
    """Flash attention in the model layout.  The tiles come from the call's
    shape alone (``_flash_blocks``): q tiles up to 512 rows, and the whole
    key length as one block where that fits the VMEM budget.

    A row right-padded to a longer key length with ``kv_mask`` comes back
    bit-identical on its valid slice to its exact-shape run: its valid keys
    split into blocks at the same places, the block holding its last valid
    key only gains masked keys, and whole blocks past it are masked."""
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    hdp = -(-hd // 128) * 128
    bq, bk = _flash_blocks(sq, sk, hdp, max(q.dtype.itemsize, k.dtype.itemsize))
    # kernel layout (B, H, S, hd)
    qt = _pad_to(_pad_to(q.transpose(0, 2, 1, 3), 128, 3), bq, 2)
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), 128, 3), bk, 2)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), 128, 3), bk, 2)
    qp = _pad_to(q_pos.astype(jnp.int32), bq, 0, value=-(10**9))
    kp = _pad_to(kv_pos.astype(jnp.int32), bk, 0, value=-1)
    km = (
        None
        if kv_mask is None
        else _pad_to(kv_mask.astype(jnp.int32), bk, 1, value=0)
    )
    out = _fa.flash_attention(
        qt, kt, vt, qp, kp,
        window=window, causal=causal, softcap=softcap, protected=protected,
        scale=hd**-0.5, block_q=bq, block_k=bk,
        interpret=interpret_mode(), kv_mask=km,
    )
    return out[:, :, :sq, :hd].transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("window", "protected", "block_k")
)
def decode_attention(
    q: Array,       # (B, 1, H, hd) or (B, H, hd)
    k: Array,       # (B, S, KV, hd) cache layout
    v: Array,
    q_pos: Array,   # scalar
    kv_pos: Array,  # (S,)
    *,
    window: int = 0,
    protected: int = 0,
    block_k: int = 128,
) -> Array:
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    gp = -(-g // 8) * 8  # pad group rows to sublane multiple
    qt = _pad_to(q.reshape(b, kvh, g, hd), 128, 3)
    if gp != g:
        qt = _pad_to(qt, gp, 2)
    qt = qt.reshape(b, kvh * gp, qt.shape[-1])
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), 128, 3), block_k, 2)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), 128, 3), block_k, 2)
    kp = _pad_to(kv_pos.astype(jnp.int32), block_k, 0, value=-1)
    out = _dec.decode_attention(
        qt, kt, vt, q_pos, kp,
        window=window, protected=protected, scale=hd**-0.5,
        block_k=block_k, interpret=interpret_mode(),
    )
    out = out.reshape(b, kvh, gp, -1)[:, :, :g, :hd].reshape(b, h, hd)
    return out[:, None] if squeeze else out


#: VMEM the selective scan's blocks may fill (v5e's default scoped limit is
#: 16 MiB)
SCAN_VMEM_BYTES = 12 << 20


def _scan_block_d(di: int, n: int, block_t: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``di`` and whose blocks fit
    ``SCAN_VMEM_BYTES``: dt and x in their dtype and y in float32, each
    double-buffered, and the float32 state, A, h0 and h_last.  ``di`` whole
    where it is no multiple of 128."""
    if di % 128:
        return di
    per_lane = 2 * block_t * (2 * itemsize + 4) + 7 * n * 4
    return max(
        (bd for bd in range(128, di + 1, 128)
         if di % bd == 0 and bd * per_lane <= SCAN_VMEM_BYTES),
        default=128,
    )


@jax.jit
def selective_scan(
    dt: Array,      # (B, S, di)
    x: Array,       # (B, S, di)
    a: Array,       # (di, N) float32
    bmat: Array,    # (B, S, N)
    c: Array,       # (B, S, N)
    h0: Array,      # (B, di, N) float32
) -> tuple[Array, Array]:
    """Mamba's selective scan with the state in VMEM: the same (y (B, S, di)
    float32, h_last (B, di, N) float32) as ``ssm.chunked_ssm_outputs`` on
    the float32 casts of its operands.  A sequence shorter than the kernel's
    time block runs one block of its own length, rounded up to the kernel's
    group; pad steps have ``dt = 0``, which leave the state as it is."""
    s, di = x.shape[1], x.shape[2]
    bt = min(_ss.BLOCK_T, -(-s // _ss.GROUP) * _ss.GROUP)
    itemsize = max(dt.dtype.itemsize, x.dtype.itemsize)
    bd = _scan_block_d(di, bmat.shape[-1], bt, itemsize)
    dt, x, bmat, c = (_pad_to(t, bt, 1) for t in (dt, x, bmat, c))
    y, h_last = _ss.selective_scan(
        dt, x, bmat, c,
        a.astype(jnp.float32).T,
        h0.astype(jnp.float32).transpose(0, 2, 1),
        block_t=bt, block_d=bd, interpret=interpret_mode(),
    )
    return y[:, :s], h_last.transpose(0, 2, 1)


@functools.partial(jax.jit, static_argnames=("block",))
def era_step(
    x: Array,          # sample, any shape
    eps_sel: Array,    # (k, *x.shape)
    t_sel: Array,      # (k,)
    e_hist: Array,     # (3, *x.shape)
    t_next: Array,
    cx: Array,
    ce: Array,
    am4: Array,        # (4,)
    *,
    block: int = 4096,
) -> tuple[Array, Array]:
    """Fused ERA step on arbitrary-shaped samples. Returns (x_next, eps_bar)."""
    shape = x.shape
    n = x.size
    # shrink the block for small samples (e.g. per-sample vmap tiles) so the
    # pad-to-block waste stays bounded; 128 keeps TPU lanes full
    block = max(128, min(block, 1 << max(n - 1, 1).bit_length()))
    lag_w = lagrange_weights(t_sel, t_next)
    xf = _pad_to(x.reshape(-1), block, 0)
    es = _pad_to(eps_sel.reshape(eps_sel.shape[0], -1), block, 1)
    eh = _pad_to(e_hist.reshape(3, -1), block, 1)
    # barriers: in interpret mode the kernel is XLA ops, and fusing the pad
    # or the slice into them rounds differently at different sample sizes,
    # which would break the bitwise padding contract
    operands = jax.lax.optimization_barrier((xf, es, lag_w, eh, am4, cx, ce))
    x_next, eps_bar = jax.lax.optimization_barrier(
        _era.era_update(*operands, block=block, interpret=interpret_mode())
    )
    return x_next[:n].reshape(shape), eps_bar[:n].reshape(shape)


def era_combine(eps_sel, t_sel, e_hist, t_next, am4=None):
    """Drop-in for repro.core.era.era_combine backed by the fused kernel
    (combine only — the DDIM x-update stays outside, for callers that
    manage x themselves)."""
    from repro.core.era import AM4

    am4 = jnp.asarray(AM4 if am4 is None else am4, jnp.float32)
    x_dummy = jnp.zeros(eps_sel.shape[1:], eps_sel.dtype)
    x_next, eps_bar = era_step(
        x_dummy, eps_sel, t_sel, e_hist, t_next,
        jnp.float32(0.0), jnp.float32(1.0), am4,
    )
    # with cx=0, ce=1 the kernel's x_next equals eps_corr
    return eps_bar, x_next


def fused_step_parity(
    shape: tuple[int, ...] = (4, 96),
    k: int = 4,
    seed: int = 0,
) -> float:
    """Max abs error of the fused `era_step` vs the reference combine + DDIM
    update on a random input (interpret mode off-TPU, compiled on TPU).
    Returns the error; callers decide the tolerance (1e-5 is comfortable in
    f32).  Runs eagerly: it converts the error to a Python float."""
    from repro.core.era import AM4, era_combine

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], shape, jnp.float32)
    eps_sel = jax.random.normal(keys[1], (k,) + shape, jnp.float32)
    e_hist = jax.random.normal(keys[2], (3,) + shape, jnp.float32)
    t_sel = jnp.linspace(0.9, 0.3, k)
    t_next = jnp.float32(0.25)
    cx, ce = jnp.float32(0.97), jnp.float32(-0.05)
    am4 = jnp.asarray(AM4, jnp.float32)
    x_next, eps_bar = era_step(x, eps_sel, t_sel, e_hist, t_next, cx, ce, am4)
    eb_ref, ec_ref = era_combine(eps_sel, t_sel, e_hist, t_next)
    x_ref = cx * x + ce * ec_ref
    err = jnp.maximum(
        jnp.max(jnp.abs(x_next - x_ref)), jnp.max(jnp.abs(eps_bar - eb_ref))
    )
    return float(err)
