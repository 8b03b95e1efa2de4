import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models.attention import (
    _chunked_sdpa,
    _naive_sdpa,
    cache_insert,
    init_cache,
    resolve_impl,
    sdpa,
)


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@settings(max_examples=15, deadline=None)
@given(
    st.integers(1, 3),           # batch
    st.sampled_from([(4, 2), (8, 4), (6, 1)]),  # (H, KV)
    st.integers(5, 40),          # Sq = Sk
    st.sampled_from([16, 32]),   # hd
    st.sampled_from([0, 7]),     # window
    st.sampled_from([3, 16]),    # chunk
)
def test_chunked_matches_naive(b, heads, s, hd, window, chunk):
    h, kv = heads
    q = _rand(0, b, s, h, hd)
    k = _rand(1, b, s, kv, hd)
    v = _rand(2, b, s, kv, hd)
    pos = jnp.arange(s)
    ref = _naive_sdpa(q, k, v, pos, pos, window=window, causal=True, softcap=0.0)
    got = _chunked_sdpa(
        q, k, v, pos, pos, window=window, causal=True, softcap=0.0, chunk=chunk
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_invalid_slots_masked():
    b, s, h, hd = 1, 8, 2, 16
    q = _rand(0, b, 1, h, hd)
    k = _rand(1, b, s, h, hd)
    v = _rand(2, b, s, h, hd)
    kv_pos = jnp.array([0, 1, 2, 3, -1, -1, -1, -1])
    out_masked = sdpa(q, k, v, jnp.array([3]), kv_pos, impl="naive")
    out_short = sdpa(
        q, k[:, :4], v[:, :4], jnp.array([3]), kv_pos[:4], impl="naive"
    )
    np.testing.assert_allclose(
        np.asarray(out_masked), np.asarray(out_short), atol=1e-5
    )


def test_ring_buffer_positions():
    cache = init_cache(1, 4, 1, 8, jnp.float32)
    for pos in range(7):
        k = jnp.full((1, 1, 1, 8), float(pos))
        cache = cache_insert(cache, k, k, jnp.int32(pos))
    # slots hold positions 4,5,6,3 (ring of 4)
    assert sorted(np.asarray(cache["pos"]).tolist()) == [3, 4, 5, 6]


def test_protected_slots_never_evicted():
    cache = init_cache(1, 6, 1, 4, jnp.float32)
    for pos in range(12):
        k = jnp.full((1, 1, 1, 4), float(pos))
        cache = cache_insert(cache, k, k, jnp.int32(pos), protected=2)
    pos_arr = np.asarray(cache["pos"])
    assert pos_arr[0] == 0 and pos_arr[1] == 1  # sinks retained
    assert set(pos_arr[2:]) == {8, 9, 10, 11}


def test_sliding_window_with_sinks():
    """Protected prefix stays visible outside the window."""
    b, s, h, hd = 1, 12, 1, 8
    q = _rand(0, b, 1, h, hd)
    k = _rand(1, b, s, h, hd)
    v = _rand(2, b, s, h, hd)
    kv_pos = jnp.arange(s)
    out = sdpa(
        q, k, v, jnp.array([11]), kv_pos,
        window=4, protected=2, impl="naive",
    )
    # equivalent dense computation over {0,1} U {8..11}
    keep = jnp.array([0, 1, 8, 9, 10, 11])
    out2 = sdpa(
        q, k[:, keep], v[:, keep], jnp.array([11]), kv_pos[keep], impl="naive"
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-5)


def test_softcap_changes_scores():
    b, s, h, hd = 1, 6, 2, 16
    q, k, v = _rand(0, b, s, h, hd), _rand(1, b, s, h, hd), _rand(2, b, s, h, hd)
    pos = jnp.arange(s)
    a = sdpa(q * 10, k, v, pos, pos, impl="naive")
    b_ = sdpa(q * 10, k, v, pos, pos, impl="naive", softcap=5.0)
    assert float(jnp.max(jnp.abs(a - b_))) > 1e-4


def test_int8_kv_cache_roundtrip():
    from repro.models.attention import _dequant, _quantize

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32)) * 3.0
    q, s = _quantize(x)
    back = _dequant(q, s, jnp.float32)
    rel = float(jnp.max(jnp.abs(back - x)) / jnp.max(jnp.abs(x)))
    assert q.dtype == jnp.int8
    assert rel < 0.02


def test_int8_kv_decode_matches_full():
    """int8 KV cache keeps decode logits within quantization noise of the
    full-precision cache (same token stream fed to both engines — token
    agreement on an untrained model is argmax-fragile and proves nothing)."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Engine, ServeConfig

    key = jax.random.PRNGKey(0)
    cfg = get_config("llama3.2-1b", smoke=True)
    m_full = build_model(cfg)
    m_q = build_model(cfg.with_(kv_quant="int8"))
    params = m_full.init(key)
    prompts = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    eng_f = Engine(m_full, ServeConfig(max_len=64))
    eng_q = Engine(m_q, ServeConfig(max_len=64))
    batch = {"tokens": prompts}
    lf, cf = eng_f.prefill_step(params, batch)
    lq, cq = eng_q.prefill_step(params, batch)
    pos = cfg.num_meta_tokens + prompts.shape[1]
    for i in range(6):
        nxt = jnp.argmax(
            lf[:, -1, : cfg.vocab_size].astype(jnp.float32), axis=-1
        ).astype(jnp.int32)
        dec = {"tokens": nxt[:, None], "pos": jnp.int32(pos + i)}
        lf, cf = eng_f.decode_step(params, cf, dec)
        lq, cq = eng_q.decode_step(params, cq, dec)
        scale = float(jnp.max(jnp.abs(lf)).astype(jnp.float32)) + 1e-6
        err = float(jnp.max(jnp.abs(lf - lq)).astype(jnp.float32)) / scale
        # ~2% per-tensor int8 noise compounds across layers and steps;
        # a scale/layout bug would blow past 1.0
        assert err < 0.2, (i, err)


# ---------------------------------------------------------------------------
# per-row kv_mask: every impl carries it natively (mixed-seq-len serving)
# ---------------------------------------------------------------------------


def _lengths_mask(s, lengths):
    return jnp.arange(s)[None, :] < jnp.asarray(lengths, jnp.int32)[:, None]


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from([(4, 2), (6, 1)]),  # (H, KV)
    st.integers(8, 40),                 # S
    st.sampled_from([0, 7]),            # window
    st.booleans(),                      # causal
    st.integers(0, 10_000),             # lengths seed
)
def test_masked_chunked_matches_masked_naive(heads, s, window, causal, lseed):
    h, kv = heads
    b = 3
    q = _rand(0, b, s, h, 16)
    k = _rand(1, b, s, kv, 16)
    v = _rand(2, b, s, kv, 16)
    pos = jnp.arange(s)
    lens = jax.random.randint(
        jax.random.PRNGKey(lseed), (b,), 0, s + 1
    ).tolist()
    lens[0] = s  # pin a full row
    mask = _lengths_mask(s, lens)
    ref = _naive_sdpa(
        q, k, v, pos, pos, window=window, causal=causal, softcap=0.0,
        kv_mask=mask,
    )
    got = _chunked_sdpa(
        q, k, v, pos, pos, window=window, causal=causal, softcap=0.0,
        chunk=16, kv_mask=mask,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_masked_pallas_and_banded_match_chunked():
    """sdpa-level wall: with kv_mask set, the pallas and banded fast paths
    agree with chunked on a windowed+sinks causal layout that exercises all
    three dispatches."""
    b, s, h, kv, hd = 3, 128, 4, 2, 32
    q = _rand(0, b, s, h, hd)
    k = _rand(1, b, s, kv, hd)
    v = _rand(2, b, s, kv, hd)
    pos = jnp.arange(s)
    mask = _lengths_mask(s, (128, 57, 0))
    kw = dict(window=32, causal=True, softcap=0.0, protected=2, kv_mask=mask)
    ref = sdpa(q, k, v, pos, pos, impl="chunked", chunk=64, **kw)
    banded = sdpa(q, k, v, pos, pos, impl="banded", **kw)  # s >= 4*window
    pallas = sdpa(q, k, v, pos, pos, impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(ref), atol=2e-5)
    assert not np.asarray(pallas[2]).any()  # all-pad row -> exact zeros


def test_fully_masked_rows_zero_on_all_impls():
    b, s, h, hd = 2, 16, 2, 16
    q, k, v = _rand(0, b, s, h, hd), _rand(1, b, s, h, hd), _rand(2, b, s, h, hd)
    pos = jnp.arange(s)
    mask = _lengths_mask(s, (0, 5))
    for impl in ("naive", "chunked", "pallas"):
        out = sdpa(q, k, v, pos, pos, causal=False, impl=impl, kv_mask=mask)
        assert not np.asarray(out[0]).any(), impl
        assert np.asarray(out[1]).any(), impl


# ---------------------------------------------------------------------------
# fallback machinery: loud, observable, and never fired by masked fast paths
# ---------------------------------------------------------------------------


def test_banded_layout_unmet_falls_back_loudly():
    import warnings as _warnings

    from repro.models import attention as A

    b, s, h, hd = 1, 16, 2, 8
    q, k, v = _rand(0, b, s, h, hd), _rand(1, b, s, h, hd), _rand(2, b, s, h, hd)
    pos = jnp.arange(s)
    events = []
    obs = A.register_fallback_observer(lambda i, r: events.append((i, r)))
    # the once-per-process warning may have fired in an earlier test: reset
    A._warned_fallbacks.discard(("banded", "banded-layout-unmet"))
    try:
        with _warnings.catch_warnings(record=True) as caught:
            _warnings.simplefilter("always")
            out = sdpa(q, k, v, pos, pos, causal=False, impl="banded")
        assert events == [("banded", "banded-layout-unmet")]
        msgs = [str(w.message) for w in caught if w.category is RuntimeWarning]
        assert any("falling back to chunked" in m for m in msgs)
        ref = sdpa(q, k, v, pos, pos, causal=False, impl="chunked")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # second hit: observer fires again, warning does not
        with _warnings.catch_warnings(record=True) as caught2:
            _warnings.simplefilter("always")
            sdpa(q, k, v, pos, pos, causal=False, impl="banded")
        assert len(events) == 2
        assert not [w for w in caught2 if w.category is RuntimeWarning]
    finally:
        A.unregister_fallback_observer(obs)


def test_masked_fast_paths_do_not_fire_fallback():
    """The whole point of the tentpole: kv_mask on pallas/banded/chunked is
    native, so no fallback observer fires for masked traffic."""
    from repro.models import attention as A

    b, s, h, hd = 2, 128, 2, 16
    q, k, v = _rand(0, b, s, h, hd), _rand(1, b, s, h, hd), _rand(2, b, s, h, hd)
    pos = jnp.arange(s)
    mask = _lengths_mask(s, (128, 40))
    events = []
    obs = A.register_fallback_observer(lambda i, r: events.append((i, r)))
    try:
        sdpa(q, k, v, pos, pos, causal=False, impl="pallas", kv_mask=mask)
        sdpa(q, k, v, pos, pos, causal=True, window=32, impl="banded",
             kv_mask=mask)
        sdpa(q, k, v, pos, pos, causal=False, impl="chunked", kv_mask=mask)
        sdpa(q, k, v, pos, pos, causal=False, impl="auto", kv_mask=mask)
    finally:
        A.unregister_fallback_observer(obs)
    assert events == []


def test_auto_attention_picks_by_platform(monkeypatch):
    """``auto`` resolves from the platform: XLA SDPA off TPU, the Pallas
    flash kernel for every multi-query call on TPU, XLA for single-token
    decode; an explicit impl is never rewritten."""
    from repro.configs import get_config

    cfg = get_config("qwen2-1.5b", smoke=True)
    assert resolve_impl(cfg, 8, 8) == "naive"
    assert resolve_impl(cfg, 4096, 4096) == "chunked"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_impl(cfg, 512, 512) == "pallas"
    assert resolve_impl(cfg, 8, 8) == "pallas"
    assert resolve_impl(cfg, 1, 512) == "naive"
    assert resolve_impl(cfg.with_(attention_impl="chunked"), 512, 512) == (
        "chunked"
    )


def test_auto_attention_trains_through_the_flash_kernel_on_tpu(monkeypatch):
    """On TPU ``auto`` runs the Pallas flash kernel in training too.  The
    kernel has no backward pass of its own; its gradient comes from the
    chunked path and matches XLA attention's gradient of the same loss."""
    from repro.configs import get_config
    from repro.core import linear_schedule
    from repro.kernels import ops
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    cfg = get_config("qwen2-1.5b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(jax.random.PRNGKey(0))
    # a non-zero eps head, so the loss reaches the attention layers
    params["eps_head"]["w"] = 0.1 * _rand(1, *params["eps_head"]["w"].shape)
    batch = {"latents": _rand(2, 2, 16, cfg.d_model)}
    loss = lambda p: dlm.loss(
        p, batch, jax.random.PRNGKey(3), linear_schedule()
    )[0]
    g_xla = jax.grad(loss)(params)

    calls = []
    flash = ops.flash_attention

    def counted(*a, **kw):
        calls.append(1)
        return flash(*a, **kw)

    # the platform says TPU; with no chip here the kernel still interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "interpret_mode", lambda: True)
    monkeypatch.setattr(ops, "flash_attention", counted)
    g_tpu = jax.grad(loss)(params)
    assert calls, "auto did not run the Pallas flash kernel"
    for a, b in zip(jax.tree.leaves(g_tpu), jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
