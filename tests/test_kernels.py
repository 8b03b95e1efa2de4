"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (deliverable c).

All kernels run in interpret mode on CPU; the same call sites compile for
TPU unchanged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.era import AM4
from repro.core.lagrange import lagrange_weights
from repro.kernels import ops, ref


def _rand(seed, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, H, KV, Sq, Sk, hd, window, causal, softcap, dtype)
    (2, 4, 2, 128, 128, 64, 0, True, 0.0, jnp.float32),
    (1, 8, 8, 256, 256, 128, 0, False, 0.0, jnp.float32),
    (2, 4, 1, 100, 100, 48, 0, True, 0.0, jnp.float32),       # MQA + ragged
    (1, 6, 3, 130, 130, 80, 32, True, 0.0, jnp.float32),      # window
    (1, 4, 4, 64, 64, 64, 16, True, 0.0, jnp.bfloat16),       # bf16
    (2, 2, 2, 96, 96, 64, 0, True, 30.0, jnp.float32),        # softcap
    # past one q tile and one key block: 3 q tiles (Sq no multiple of
    # either tile) and 2 key blocks, GQA 6 and 5
    (1, 6, 1, 1100, 1100, 128, 0, False, 0.0, jnp.float32),
    (1, 5, 1, 1300, 1300, 64, 256, True, 0.0, jnp.float32),
    (1, 10, 2, 1040, 1040, 128, 0, False, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_ref(case):
    b, h, kv, sq, sk, hd, window, causal, cap, dtype = case
    q = _rand(0, (b, sq, h, hd), dtype)
    k = _rand(1, (b, sk, kv, hd), dtype)
    v = _rand(2, (b, sk, kv, hd), dtype)
    qpos, kpos = jnp.arange(sq), jnp.arange(sk)
    out = ops.flash_attention(
        q, k, v, qpos, kpos, window=window, causal=causal, softcap=cap
    )
    r = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).astype(jnp.float32),
        k.transpose(0, 2, 1, 3).astype(jnp.float32),
        v.transpose(0, 2, 1, 3).astype(jnp.float32),
        qpos, kpos, window=window, causal=causal, softcap=cap,
    ).transpose(0, 2, 1, 3)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(r, np.float32), atol=atol
    )


@settings(max_examples=8, deadline=None)
@given(
    st.integers(1, 2),
    st.sampled_from([(2, 1), (4, 2), (4, 4)]),
    st.integers(17, 150),
    st.sampled_from([32, 64, 96]),
    st.sampled_from([0, 24]),
)
def test_flash_attention_hypothesis(b, heads, s, hd, window):
    h, kv = heads
    q = _rand(3, (b, s, h, hd))
    k = _rand(4, (b, s, kv, hd))
    v = _rand(5, (b, s, kv, hd))
    pos = jnp.arange(s)
    out = ops.flash_attention(q, k, v, pos, pos, window=window)
    r = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pos, pos, window=window,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (2, 8, 2, 256, 64, 0, 0, jnp.float32),
    (1, 4, 4, 300, 128, 64, 0, jnp.float32),
    (2, 6, 3, 200, 80, 32, 4, jnp.float32),
    (1, 25, 5, 130, 64, 48, 8, jnp.float32),   # hymba head counts
    (2, 8, 1, 256, 64, 0, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_vs_ref(case):
    b, h, kv, s, hd, window, prot, dtype = case
    q = _rand(0, (b, h, hd), dtype)
    k = _rand(1, (b, s, kv, hd), dtype)
    v = _rand(2, (b, s, kv, hd), dtype)
    kv_pos = jnp.where(jnp.arange(s) < s - 10, jnp.arange(s), -1)
    qpos = jnp.int32(s - 11)
    out = ops.decode_attention(
        q, k, v, qpos, kv_pos, window=window, protected=prot
    )
    r = ref.decode_attention_ref(
        q.astype(jnp.float32),
        k.transpose(0, 2, 1, 3).astype(jnp.float32),
        v.transpose(0, 2, 1, 3).astype(jnp.float32),
        qpos, kv_pos, window=window, protected=prot,
    )
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(r, np.float32), atol=atol
    )


def test_decode_matches_flash_single_row():
    """Decode kernel == flash kernel with Sq=1 on the same cache."""
    b, h, kv, s, hd = 1, 4, 2, 128, 64
    q = _rand(0, (b, h, hd))
    k = _rand(1, (b, s, kv, hd))
    v = _rand(2, (b, s, kv, hd))
    kv_pos = jnp.arange(s)
    qpos = jnp.int32(s - 1)
    dec = ops.decode_attention(q, k, v, qpos, kv_pos)
    fl = ops.flash_attention(
        q[:, None], k, v, jnp.array([s - 1]), kv_pos, causal=True
    )[:, 0]
    np.testing.assert_allclose(np.asarray(dec), np.asarray(fl), atol=3e-5)


# ---------------------------------------------------------------------------
# fused ERA update
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    st.integers(2, 6),                     # k order
    st.sampled_from([(64,), (3, 17, 5), (2, 130)]),
    st.sampled_from([64, 256]),
)
def test_era_step_vs_ref(k_order, shape, block):
    x = _rand(0, shape)
    eps_sel = _rand(1, (k_order,) + shape)
    t_sel = jnp.linspace(0.9, 0.2, k_order)
    e_hist = _rand(2, (3,) + shape)
    t_next = jnp.float32(0.15)
    cx, ce = jnp.float32(0.97), jnp.float32(-0.05)
    am4 = jnp.asarray(AM4, jnp.float32)
    xn, eb = ops.era_step(x, eps_sel, t_sel, e_hist, t_next, cx, ce, am4, block=block)
    w = lagrange_weights(t_sel, t_next)
    xr, er = ref.era_update_ref(
        x.reshape(-1), eps_sel.reshape(k_order, -1), w,
        e_hist.reshape(3, -1), am4, cx, ce,
    )
    np.testing.assert_allclose(np.asarray(xn).reshape(-1), np.asarray(xr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(eb).reshape(-1), np.asarray(er), atol=2e-5)


def test_era_combine_drop_in():
    from repro.core.era import era_combine as core_combine

    k_order = 4
    eps_sel = _rand(1, (k_order, 8, 4))
    t_sel = jnp.array([0.9, 0.7, 0.5, 0.3])
    e_hist = _rand(2, (3, 8, 4))
    t_next = jnp.float32(0.25)
    eb1, ec1 = core_combine(eps_sel, t_sel, e_hist, t_next)
    eb2, ec2 = ops.era_combine(eps_sel, t_sel, e_hist, t_next)
    np.testing.assert_allclose(np.asarray(eb1), np.asarray(eb2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ec1), np.asarray(ec2), atol=2e-5)


# ---------------------------------------------------------------------------
# masked flash attention (per-row kv_mask operand — mixed-seq-len serving)
# ---------------------------------------------------------------------------


def _ragged_mask(s, lengths):
    return jnp.arange(s)[None, :] < jnp.asarray(lengths, jnp.int32)[:, None]


MASKED_FLASH_CASES = [
    # (H, KV, S, hd, window, causal, softcap, protected, lengths)
    # lengths sweep ragged rows including all-pad (0) and full-length rows
    (4, 2, 128, 64, 0, True, 0.0, 0, (128, 57, 0)),
    (4, 2, 128, 64, 0, False, 0.0, 0, (128, 57, 0)),     # denoiser layout
    (8, 8, 256, 128, 0, False, 0.0, 0, (200, 1)),
    (4, 1, 100, 48, 0, True, 0.0, 0, (99, 31)),          # MQA + ragged shape
    (6, 3, 130, 80, 32, True, 0.0, 4, (120, 77)),        # window + sinks
    (2, 2, 96, 64, 0, True, 30.0, 0, (96, 5)),           # softcap
    # past one q tile and one key block (3 q tiles, 2 key blocks), GQA 6
    # and 5, rows ending in either block or before the second q tile
    (6, 1, 1100, 128, 0, False, 0.0, 0, (1100, 700, 0)),
    (5, 1, 1300, 64, 256, True, 0.0, 4, (1300, 1030, 300)),
]


@pytest.mark.parametrize("case", MASKED_FLASH_CASES)
def test_masked_flash_attention_vs_masked_refs(case):
    """Masked Pallas kernel vs BOTH masked oracles: the pure-jnp ref and
    the masked chunked-SDPA streaming softmax.  All-pad rows come back
    exactly zero on every impl."""
    from repro.models.attention import _chunked_sdpa

    h, kv, s, hd, window, causal, cap, prot, lengths = case
    b = len(lengths)
    q = _rand(0, (b, s, h, hd))
    k = _rand(1, (b, s, kv, hd))
    v = _rand(2, (b, s, kv, hd))
    pos = jnp.arange(s)
    mask = _ragged_mask(s, lengths)
    out = ops.flash_attention(
        q, k, v, pos, pos, kv_mask=mask,
        window=window, causal=causal, softcap=cap, protected=prot,
    )
    r = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pos, pos,
        window=window, causal=causal, softcap=cap, protected=prot,
        kv_mask=mask,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-5)
    c = _chunked_sdpa(
        q, k, v, pos, pos, window=window, causal=causal, softcap=cap,
        chunk=64, protected=prot, kv_mask=mask,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(c), atol=2e-5)
    for row, n in enumerate(lengths):
        if n == 0:
            assert not np.asarray(out[row]).any(), "all-pad row must be zero"


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from([(2, 1), (4, 2), (4, 4), (6, 3)]),   # GQA group sizes
    st.integers(17, 150),                                # seq len
    st.sampled_from([32, 64, 96]),                       # head dim
    st.sampled_from([(0, 0, True), (0, 0, False), (24, 0, True),
                     (24, 4, True)]),                    # window/sinks/causal
    st.sampled_from([0.0, 20.0]),                        # softcap
    st.integers(0, 10_000),                              # lengths seed
)
def test_masked_flash_attention_hypothesis(heads, s, hd, wpc, cap, lseed):
    """Hypothesis sweep of the masked kernel across GQA group sizes,
    window/causal, softcap, protected sinks, and ragged per-row lengths —
    always including an all-pad row and a full-length row."""
    h, kv = heads
    window, prot, causal = wpc
    b = 4
    q = _rand(6, (b, s, h, hd))
    k = _rand(7, (b, s, kv, hd))
    v = _rand(8, (b, s, kv, hd))
    pos = jnp.arange(s)
    lkey = jax.random.PRNGKey(lseed)
    lens = jax.random.randint(lkey, (b,), 0, s + 1).tolist()
    lens[0], lens[1] = s, 0      # pin the edge rows
    mask = _ragged_mask(s, lens)
    out = ops.flash_attention(
        q, k, v, pos, pos, kv_mask=mask,
        window=window, causal=causal, softcap=cap, protected=prot,
    )
    r = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), pos, pos,
        window=window, causal=causal, softcap=cap, protected=prot,
        kv_mask=mask,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=3e-5)
    assert not np.asarray(out[1]).any()


def test_masked_flash_padding_invariance_bitwise():
    """The serving property the mask exists for: a row right-padded from L
    to S with kv_mask runs BIT-IDENTICAL (on its valid slice) to the same
    row's exact-shape unmasked kernel run — extra fully-masked kv blocks
    rescale the online-softmax state by exp(0) == 1.0 exactly, and a wider
    key block whose added keys are all masked sums the same terms.  The
    later shapes cross the tile rule's boundaries: one key block of 384 or
    768 against one of 1024, and one of 384 or two of 1024 against two or
    three of 1024 (GQA 3 and 5, hd 128)."""
    b = 1
    for h, kv, hd, s, lengths in (
        (4, 2, 64, 96, (1, 31, 64, 95)),
        (6, 2, 128, 1024, (300, 700)),
        (10, 2, 128, 3072, (300, 1500)),
    ):
        for L in lengths:
            q = _rand(10, (b, s, h, hd))
            k = _rand(11, (b, s, kv, hd))
            v = _rand(12, (b, s, kv, hd))
            for causal in (False, True):
                exact = ops.flash_attention(
                    q[:, :L], k[:, :L], v[:, :L],
                    jnp.arange(L), jnp.arange(L), causal=causal,
                )
                padded = ops.flash_attention(
                    q, k, v, jnp.arange(s), jnp.arange(s),
                    kv_mask=_ragged_mask(s, [L]), causal=causal,
                )
                np.testing.assert_array_equal(
                    np.asarray(padded[:, :L]), np.asarray(exact),
                    err_msg=f"S={s} L={L} causal={causal}",
                )


@pytest.mark.parametrize(
    "hd, itemsize", [(128, 2), (256, 2), (128, 4), (256, 4), (512, 2)]
)
def test_flash_tile_rule(hd, itemsize):
    """``ops._flash_blocks`` over a grid of shapes: tiles aligned, within the
    VMEM budget, q padding under one 16-row step a tile, and the cuts
    between key blocks inside a valid prefix the same at the row's own
    length as at any length it is padded to."""
    lengths = (1, 17, 100, 128, 300, 512, 513, 700, 1024, 1025, 1100, 1500, 2048,
               3000, 4096)
    for sq in lengths:
        bq, bk = ops._flash_blocks(sq, sq, hd, itemsize)
        assert bq % 16 == 0 and bq <= ops.FLASH_BLOCK_Q
        assert bk % 128 == 0 and bk <= -(-sq // 128) * 128
        assert ops._flash_vmem_bytes(bq, bk, hd, itemsize) <= ops.FLASH_VMEM_BYTES
        assert -(-sq // bq) * bq - sq < 16 * -(-sq // ops.FLASH_BLOCK_Q)

    def cuts(valid, padded_to):
        bk = ops._flash_blocks(padded_to, padded_to, hd, itemsize)[1]
        return list(range(bk, valid, bk))

    for valid in lengths:
        for padded_to in (n for n in lengths if n >= valid):
            assert cuts(valid, padded_to) == cuts(valid, valid), (valid, padded_to)
    if (hd, itemsize) in ((128, 2), (256, 2), (128, 4)):
        # the cells' calls and qwen2's float32 twin: one key block at 1024
        assert ops._flash_blocks(1024, 1024, hd, itemsize) == (512, 1024)


def test_unmasked_flash_unchanged_by_mask_plumbing():
    """kv_mask=None and an all-valid kv_mask agree with each other and the
    unmasked oracle (the mask operand costs nothing when absent)."""
    b, h, kv, s, hd = 2, 4, 2, 128, 64
    q, k, v = _rand(0, (b, s, h, hd)), _rand(1, (b, s, kv, hd)), _rand(2, (b, s, kv, hd))
    pos = jnp.arange(s)
    out_none = ops.flash_attention(q, k, v, pos, pos)
    out_full = ops.flash_attention(
        q, k, v, pos, pos, kv_mask=jnp.ones((b, s), bool)
    )
    np.testing.assert_array_equal(np.asarray(out_none), np.asarray(out_full))


# ---------------------------------------------------------------------------
# selective scan (Mamba)
# ---------------------------------------------------------------------------


def _scan_inputs(b, s, di, n=16, dtype=jnp.float32):
    dt = (0.5 * jax.nn.softplus(_rand(20, (b, s, di)))).astype(dtype)
    x = _rand(21, (b, s, di), dtype)
    bmat, c = _rand(22, (b, s, n), dtype), _rand(23, (b, s, n), dtype)
    a = -jnp.exp(0.5 * _rand(24, (di, n)))
    h0 = _rand(25, (b, di, n))
    return dt, x, a, bmat, c, h0


def _scan_f64(dt, x, a, bmat, c, h0):
    """Step-by-step float64 recurrence: h_t = exp(dt_t A) h + dt_t x_t B_t,
    y_t = <h_t, C_t>."""
    dt, x, a, bmat, c, h = (np.asarray(t, np.float64) for t in (dt, x, a, bmat, c, h0))
    ys = []
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t, :, None] * a) * h + (
            (dt[:, t] * x[:, t])[..., None] * bmat[:, t, None, :]
        )
        ys.append(np.einsum("bdn,bn->bd", h, c[:, t]))
    return np.stack(ys, axis=1), h


SCAN_CASES = [
    # (B, S, di, dtype): S=300 spans three time blocks of 128, the last
    # padded; di=384 is no multiple of 256 or 512
    (b, s, di, jnp.float32)
    for b in (1, 3) for s in (1, 7, 256, 300) for di in (256, 384)
] + [(3, 300, 384, jnp.bfloat16)]


@pytest.mark.parametrize(
    "case", SCAN_CASES, ids=lambda c: f"b{c[0]}-s{c[1]}-d{c[2]}-{jnp.dtype(c[3]).name}"
)
def test_selective_scan_vs_chunked_and_f64(case):
    """The kernel (interpret mode) against the chunked jnp scan it replaces
    on TPU and a float64 step-by-step recurrence, from a nonzero h0, with
    h_last checked too.  bf16 operands are cast to float32 exactly."""
    from repro.models.ssm import chunked_ssm_outputs

    b, s, di, dtype = case
    dt, x, a, bmat, c, h0 = _scan_inputs(b, s, di, dtype=dtype)
    y, h_last = ops.selective_scan(dt, x, a, bmat, c, h0)
    assert y.shape == (b, s, di) and y.dtype == jnp.float32
    assert h_last.shape == (b, di, 16) and h_last.dtype == jnp.float32
    f32 = lambda t: t.astype(jnp.float32)
    y_c, h_c = chunked_ssm_outputs(f32(dt), f32(x), a, f32(bmat), f32(c), h0, 32)
    y_64, h_64 = _scan_f64(dt, x, a, bmat, c, h0)
    for got, want in ((y, y_c), (h_last, h_c), (y, y_64), (h_last, h_64)):
        scale = float(np.max(np.abs(want)))
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(want, np.float64),
            rtol=0, atol=2e-6 * scale,
        )
