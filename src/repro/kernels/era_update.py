"""Fused ERA-Solver update step (the paper's per-step non-network math).

Per sampling step, ERA-Solver touches image/latent-sized tensors several
times: k Lagrange-buffer reads for the predictor combine (Eq. 13/14), three
history reads for the Adams--Moulton corrector (Eq. 11), and the DDIM
x-update (Eq. 8).  Composed naively that is ~(k+5) HBM round trips over the
sample; fused here it is a single pass — each operand is read once from HBM
into a VMEM tile, and x_{i+1} / eps_bar are written once.

Grid: 1-D over flattened-sample blocks.  Scalar operands (Lagrange weights,
AM4 coefficients, DDIM cx/ce) ride in SMEM via PrefetchScalarGridSpec so
they are resident before the tile loop starts.

This kernel is ERA's step: ``repro.kernels.ops.era_step`` compiles it on
TPU and runs it in interpret mode elsewhere, and the tests hold it to the
pure-jnp reference combine (``repro.kernels.ops.fused_step_parity``).
Per-sample ERS batches vmap this kernel; with scalar-prefetch operands the
pallas batching rule lowers that to a loop of one kernel call per row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _era_kernel(
    lag_w_ref,   # SMEM (k,)
    am4_ref,     # SMEM (4,)
    cxce_ref,    # SMEM (2,)
    x_ref,       # (bs,)
    eps_sel_ref, # (k, bs)
    e_hist_ref,  # (3, bs)
    x_out_ref,   # (bs,)
    eps_bar_ref, # (bs,)
    *,
    k: int,
):
    x = x_ref[...].astype(jnp.float32)
    eps_bar = jnp.zeros_like(x)
    for m in range(k):  # k static, fully unrolled vector FMA chain
        eps_bar += lag_w_ref[m] * eps_sel_ref[m, :].astype(jnp.float32)
    eps_corr = (
        am4_ref[0] * eps_bar
        + am4_ref[1] * e_hist_ref[0, :].astype(jnp.float32)
        + am4_ref[2] * e_hist_ref[1, :].astype(jnp.float32)
        + am4_ref[3] * e_hist_ref[2, :].astype(jnp.float32)
    )
    x_out_ref[...] = (cxce_ref[0] * x + cxce_ref[1] * eps_corr).astype(
        x_out_ref.dtype
    )
    eps_bar_ref[...] = eps_bar.astype(eps_bar_ref.dtype)


def era_update(
    x: jax.Array,        # (N,) flattened sample
    eps_sel: jax.Array,  # (k, N)
    lag_w: jax.Array,    # (k,)
    e_hist: jax.Array,   # (3, N)
    am4: jax.Array,      # (4,)
    cx: jax.Array,       # scalar
    ce: jax.Array,       # scalar
    *,
    block: int = 4096,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (x_next, eps_bar). N must be a multiple of `block` (ops.py
    pads)."""
    n = x.shape[0]
    kk = eps_sel.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)

    kernel = functools.partial(_era_kernel, k=kk)
    scalars = (
        lag_w.astype(jnp.float32),
        am4.astype(jnp.float32),
        jnp.stack([cx, ce]).astype(jnp.float32),
    )
    x_next, eps_bar = pl.pallas_call(
        kernel,
        name="era_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block,), lambda i, *_: (i,)),
                pl.BlockSpec((kk, block), lambda i, *_: (0, i)),
                pl.BlockSpec((3, block), lambda i, *_: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((block,), lambda i, *_: (i,)),
                pl.BlockSpec((block,), lambda i, *_: (i,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n,), x.dtype),
            jax.ShapeDtypeStruct((n,), x.dtype),
        ],
        interpret=interpret,
    )(*scalars, x, eps_sel, e_hist)
    return x_next, eps_bar
