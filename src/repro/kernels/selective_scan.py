"""Mamba's selective scan, Pallas TPU kernel (sequence path).

Computes what :func:`repro.models.ssm.chunked_ssm_outputs` returns, with
the recurrence swept over time and the state kept on chip:

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) (x) B_t,
    y_t = sum_n h_t[:, n] * C_t[n].

Grid ``(batch, d_inner // block_d, S // block_t)``, time innermost and
sequential: the (N, block_d) float32 state lives in VMEM scratch, is
loaded from ``h0`` at the first time block and carried across the rest,
and ``h_last`` is written at the last.  d_inner rides the lanes, the
state dimension N the sublanes.  Each step reads one row of dt and x and
one of B and C and writes one row of y, so the kernel's HBM traffic is
its inputs and outputs once; no (B, S, d_inner, N) tensor exists.

Inside a time block a ``fori_loop`` walks groups of ``GROUP`` steps: one
(GROUP, block_d) tile each of dt and x, and the (GROUP, N) slabs of B and
C transposed in VMEM to (N, GROUP), whose static columns broadcast across
the lanes.  dt, x, B and C may come in their compute dtype and are cast
to float32 on chip; the state, ``exp(dt * A)``, the products and the
readout are float32.  The sweep is strictly sequential, so no output
depends on a later position or on the sequence length: a right-padded
row's prefix is bitwise the exact-length run's.  Padding with ``dt = 0``
(and finite x, B) is an identity step: ``exp(0) = 1`` and ``0 * x = 0``.

Operand layouts (the caller, ``ops.selective_scan``, makes them): dt, x
(B, S, di); B, C (B, S, N); A transposed to (N, di); h0 and h_last
(B, N, di).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: steps per inner-loop iteration: one bf16 (16, 128) tile of dt and x
GROUP = 16
#: steps per time block (grid step); on v5e the kernel's time at hymba-1.5b's
#: shapes is the same from 64 to 256
BLOCK_T = 128


def _scan_kernel(
    dt_ref,     # (1, bt, bd)
    x_ref,      # (1, bt, bd)
    b_ref,      # (1, bt, N)
    c_ref,      # (1, bt, N)
    a_ref,      # (N, bd) float32
    h0_ref,     # (1, N, bd) float32
    y_ref,      # (1, bt, bd) float32
    hl_ref,     # (1, N, bd) float32
    h_ref,      # (N, bd) float32 scratch: the carried state
    *,
    nt: int,
):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    a = a_ref[...]
    bt = dt_ref.shape[1]

    def group(g, h):
        r = pl.multiple_of(g * GROUP, GROUP)
        dt = dt_ref[0, pl.ds(r, GROUP), :].astype(jnp.float32)   # (G, bd)
        dtx = dt * x_ref[0, pl.ds(r, GROUP), :].astype(jnp.float32)
        bcol = b_ref[0, pl.ds(r, GROUP), :].astype(jnp.float32).T  # (N, G)
        ccol = c_ref[0, pl.ds(r, GROUP), :].astype(jnp.float32).T
        rows = []
        for j in range(GROUP):
            h = jnp.exp(dt[j : j + 1] * a) * h + dtx[j : j + 1] * bcol[:, j : j + 1]
            rows.append(jnp.sum(h * ccol[:, j : j + 1], axis=0, keepdims=True))
        y_ref[0, pl.ds(r, GROUP), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bt // GROUP, group, h_ref[...])

    @pl.when(it == nt - 1)
    def _finalize():
        hl_ref[0] = h_ref[...]


def selective_scan(
    dt: jax.Array,      # (B, S, di)
    x: jax.Array,       # (B, S, di)
    bmat: jax.Array,    # (B, S, N)
    c: jax.Array,       # (B, S, N)
    a_t: jax.Array,     # (N, di) float32
    h0_t: jax.Array,    # (B, N, di) float32
    *,
    block_t: int,
    block_d: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw Pallas call: S a multiple of ``block_t`` (itself of ``GROUP``),
    di of ``block_d`` (see ops.py).  Returns (y (B, S, di) float32,
    h_last (B, N, di) float32)."""
    b, s, di = x.shape
    n = bmat.shape[-1]
    assert s % block_t == 0 and block_t % GROUP == 0, (s, block_t)
    assert di % block_d == 0, (di, block_d)
    nt = s // block_t
    seq = pl.BlockSpec((1, block_t, block_d), lambda ib, idd, it: (ib, it, idd))
    slab = pl.BlockSpec((1, block_t, n), lambda ib, idd, it: (ib, it, 0))
    state = pl.BlockSpec((1, n, block_d), lambda ib, idd, it: (ib, 0, idd))
    return pl.pallas_call(
        functools.partial(_scan_kernel, nt=nt),
        name="selective_scan",
        grid=(b, di // block_d, nt),
        in_specs=[
            seq,
            seq,
            slab,
            slab,
            pl.BlockSpec((n, block_d), lambda ib, idd, it: (0, idd)),
            state,
        ],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, di), jnp.float32),
            jax.ShapeDtypeStruct((b, n, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(dt, x, bmat, c, a_t, h0_t)
