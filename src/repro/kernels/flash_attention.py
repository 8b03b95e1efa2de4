"""Flash attention, Pallas TPU kernel (prefill / training path).

Canonical TPU online-softmax pattern: 3-D grid ``(batch*heads, q_blocks,
kv_blocks)`` iterated sequentially on-core; the (acc, m, l) state lives in
VMEM scratch and persists across the innermost kv dimension.  The ops.py
wrapper pads head_dim to a multiple of 128 and picks the tiles from the
call's shape (``ops._flash_blocks``): q tiles up to 512 rows, and the whole
key length as one kv block where such a tile fits the VMEM budget.  With
one kv block the k/v block index stays the same from one grid step to the
next, over the q tiles of a head and the G heads of a GQA group, so the
pipeline fetches a head's K and V once, not once per q tile.  GQA is
expressed in the k/v BlockSpec index maps (q head h reads kv head h // G),
so no KV replication is materialized in HBM.

Masking is positional, matching :func:`repro.kernels.ref.flash_attention_ref`:
q_pos / kv_pos carry absolute positions (-1 = invalid slot), and
window/causal/protected (attention-sink) predicates are fused into the
score block.  An optional per-row ``kv_mask`` ((B, Sk), nonzero = valid
key) is folded into per-row key positions before the call (a masked key
gets position -1), so right-padded mixed-seq-len batches run this kernel
instead of falling back to chunked SDPA: masked-out keys contribute
exp(-inf)=0 to the online softmax, and a kv block whose keys are all masked
leaves (acc, m, l) bitwise unchanged — a padded batch's valid positions
compute exactly the unpadded batch's math.  That holds as long as a row's
valid keys split into kv blocks at the same places whatever the row is
padded to, which the wrapper's tile rule keeps: padding only widens the
block that holds a row's last valid key with masked keys, and adds fully
masked blocks after it.

Operand layouts follow the TPU tiling rule (the last two dims of every
block are multiples of (8, 128) or span the whole array dim): query
positions ride as a (Sq, 1) column in (block_q, 1) slabs, per-row key
positions as a (B, 1, Sk) array in (1, 1, block_k) slabs, and the softmax
state (m, l) lives in (block_q, 1) scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    qpos_ref,   # (bq, 1) query positions
    kpos_ref,   # (1, 1, bk) this batch row's key positions (-1 = masked)
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *,
    scale: float,
    window: int,
    causal: bool,
    softcap: float,
    protected: int,
    nk: int,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                        # (bq, hd)
    k = k_ref[0]                                        # (bk, hd)
    v = v_ref[0]                                        # (bk, hd)
    # float32 operands contract in full float32, not in bf16 passes
    prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32,
    ) * scale                                           # (bq, bk)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    qp = qpos_ref[...]                                  # (bq, 1)
    kp = kpos_ref[0]                                    # (1, bk)
    valid = kp >= 0
    if causal:
        valid &= kp <= qp
    if window > 0:
        in_w = kp > qp - window
        if protected > 0:
            in_w |= kp < protected
        valid &= in_w
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                 # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32,
    )
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, :, :] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(
            o_ref.dtype
        )


def flash_attention(
    q: jax.Array,       # (B, H, Sq, hd)
    k: jax.Array,       # (B, KV, Sk, hd)
    v: jax.Array,       # (B, KV, Sk, hd)
    q_pos: jax.Array,   # (Sq,) int32
    kv_pos: jax.Array,  # (Sk,) int32
    *,
    window: int = 0,
    causal: bool = True,
    softcap: float = 0.0,
    protected: int = 0,
    scale: float | None = None,   # defaults to hd**-0.5 (pre-padding value)
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    kv_mask: jax.Array | None = None,  # (B, Sk), nonzero = valid key
) -> jax.Array:
    """Raw Pallas call: shapes must already be block-aligned (see ops.py)."""
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    grid = (b * h, nq, nk)

    # per-row key positions: a masked key is an invalid (-1) slot
    kpos = jnp.broadcast_to(kv_pos.astype(jnp.int32), (b, sk))
    if kv_mask is not None:
        assert kv_mask.shape == (b, sk), (kv_mask.shape, b, sk)
        kpos = jnp.where(kv_mask != 0, kpos, -1)

    def kv_index(bh, iq, ik):
        return ((bh // h) * kvh + (bh % h) // g, ik, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=hd**-0.5 if scale is None else scale,
        window=window,
        causal=causal,
        softcap=softcap,
        protected=protected,
        nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda bh, iq, ik: (iq, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, iq, ik: (bh // h, 0, ik)),
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        q_pos.astype(jnp.int32).reshape(sq, 1),
        kpos.reshape(b, 1, sk),
        q.reshape(b * h, sq, hd),
        k.reshape(b * kvh, sk, hd),
        v.reshape(b * kvh, sk, hd),
    )
    return out.reshape(b, h, sq, hd)
