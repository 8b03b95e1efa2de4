import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.ssm import (
    chunked_linear_scan,
    mamba,
    mamba_init_state,
    mamba_specs,
    mlstm_chunkwise,
    mlstm_step,
    mlstm_zero_state,
)
from repro.models import layers as L


def _mlstm_inputs(seed=0, B=2, S=33, nh=3, hd=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, nh, hd))
    k = jax.random.normal(ks[1], (B, S, nh, hd))
    v = jax.random.normal(ks[2], (B, S, nh, hd))
    ip = jax.random.normal(ks[3], (B, S, nh)) * 2
    lf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, S, nh)) * 2)
    return q, k, v, ip, lf


def _mlstm_sequential_ref(q, k, v, ip, lf):
    B, S, nh, hd = q.shape
    C = np.zeros((B, nh, hd, hd))
    n = np.zeros((B, nh, hd))
    hs = []
    qf, kf, vf = (np.asarray(x, np.float64) for x in (q, k, v))
    ipn, lfn = np.asarray(ip, np.float64), np.asarray(lf, np.float64)
    for t in range(S):
        f, i = np.exp(lfn[:, t]), np.exp(ipn[:, t])
        C = C * f[..., None, None] + (i[..., None] * kf[:, t])[..., :, None] * vf[:, t][..., None, :]
        n = n * f[..., None] + i[..., None] * kf[:, t]
        den = np.maximum(np.abs(np.sum(n * qf[:, t], -1)), 1.0)
        hs.append(np.einsum("bnde,bnd->bne", C, qf[:, t]) / den[..., None])
    return np.stack(hs, 1)


@pytest.mark.parametrize("chunk", [1, 8, 33, 64])
def test_mlstm_chunkwise_matches_sequential(chunk):
    q, k, v, ip, lf = _mlstm_inputs()
    ref = _mlstm_sequential_ref(q, k, v, ip, lf)
    h, _ = mlstm_chunkwise(q, k, v, ip, lf, mlstm_zero_state(2, 3, 8), chunk)
    np.testing.assert_allclose(np.asarray(h), ref, atol=1e-4)


def test_mlstm_step_matches_chunkwise():
    q, k, v, ip, lf = _mlstm_inputs(S=17)
    h_all, _ = mlstm_chunkwise(q, k, v, ip, lf, mlstm_zero_state(2, 3, 8), 8)
    st = mlstm_zero_state(2, 3, 8)
    for t in range(17):
        h1, st = mlstm_step(
            q[:, t : t + 1], k[:, t : t + 1], v[:, t : t + 1],
            ip[:, t : t + 1], lf[:, t : t + 1], st,
        )
        np.testing.assert_allclose(
            np.asarray(h1[:, 0]), np.asarray(h_all[:, t]), atol=1e-4
        )


def test_mlstm_state_carry_across_chunks():
    """Processing [0:S] at once == processing [0:m] then [m:S]."""
    q, k, v, ip, lf = _mlstm_inputs(S=24)
    full, _ = mlstm_chunkwise(q, k, v, ip, lf, mlstm_zero_state(2, 3, 8), 8)
    h1, st = mlstm_chunkwise(
        q[:, :10], k[:, :10], v[:, :10], ip[:, :10], lf[:, :10],
        mlstm_zero_state(2, 3, 8), 8,
    )
    h2, _ = mlstm_chunkwise(
        q[:, 10:], k[:, 10:], v[:, 10:], ip[:, 10:], lf[:, 10:], st, 8
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([h1, h2], 1)), np.asarray(full), atol=1e-4
    )


def test_linear_scan_vs_numpy():
    key = jax.random.PRNGKey(3)
    a = jax.random.uniform(key, (2, 19, 5))
    b = jax.random.normal(key, (2, 19, 5))
    hs, hl = chunked_linear_scan(a, b, jnp.zeros((2, 5)), 4)
    h = np.zeros((2, 5))
    for t in range(19):
        h = np.asarray(a[:, t]) * h + np.asarray(b[:, t])
        np.testing.assert_allclose(np.asarray(hs[:, t]), h, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), h, atol=1e-5)


def test_mamba_seq_vs_step_decode():
    """Full-sequence mamba == token-by-token recurrent decode."""
    cfg = get_config("hymba-1.5b", smoke=True)
    p = L.init_params(mamba_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    full, _ = mamba(p, x, cfg)
    st = mamba_init_state(cfg, 2, jnp.float32)
    outs = []
    for t in range(12):
        o, st = mamba(p, x[:, t : t + 1], cfg, state=st, mode="decode")
        outs.append(o[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(full), atol=2e-4
    )


def test_mamba_trains_through_the_selective_scan_kernel_on_tpu(monkeypatch):
    """On TPU a Mamba sequence runs the Pallas selective scan.  The kernel
    has no backward pass of its own; its gradient comes from the chunked
    scan and matches the jnp path's gradient of the same loss."""
    from repro.kernels import ops

    cfg = get_config("hymba-1.5b", smoke=True)
    p = L.init_params(mamba_specs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model), cfg.dtype)
    loss = lambda p, x: jnp.sum(jnp.sin(mamba(p, x, cfg)[0]))
    g_jnp = jax.grad(loss, argnums=(0, 1))(p, x)

    calls = []
    scan = ops.selective_scan

    def counted(*a, **kw):
        calls.append(1)
        return scan(*a, **kw)

    # the platform says TPU; with no chip here the kernel still interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "interpret_mode", lambda: True)
    monkeypatch.setattr(ops, "selective_scan", counted)
    g_tpu = jax.grad(loss, argnums=(0, 1))(p, x)
    assert calls, "mamba did not run the selective scan kernel"
    for a, b in zip(jax.tree.leaves(g_tpu), jax.tree.leaves(g_jnp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4,
            atol=1e-5 * float(np.max(np.abs(np.asarray(b)))),
        )
