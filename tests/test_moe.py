import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models.moe import moe_ffn, moe_specs


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("mixtral-8x7b", smoke=True)
    p = L.init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    return cfg, p, x


def test_dropless_dropping_matches_dense_mix(setup):
    cfg, p, x = setup
    dense_cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, dispatch="dense_mix"))
    drop_cfg = cfg.with_(
        moe=dataclasses.replace(
            cfg.moe,
            dispatch="dropping",
            capacity_factor=float(cfg.moe.num_experts) / cfg.moe.top_k + 1,
        )
    )
    ref, aux_ref = moe_ffn(p, x, dense_cfg)
    got, aux_got = moe_ffn(p, x, drop_cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)
    # aux is averaged per dispatch group vs globally -> close, not identical
    assert abs(float(aux_ref["moe_aux"]) - float(aux_got["moe_aux"])) < 0.05


def test_capacity_drops_reduce_output_norm(setup):
    """Tight capacity drops tokens -> strictly less routed mass."""
    cfg, p, x = setup
    tight = cfg.with_(
        moe=dataclasses.replace(
            cfg.moe, dispatch="dropping", capacity_factor=0.25
        )
    )
    loose = cfg.with_(
        moe=dataclasses.replace(
            cfg.moe, dispatch="dropping", capacity_factor=8.0
        )
    )
    out_t, _ = moe_ffn(p, x, tight)
    out_l, _ = moe_ffn(p, x, loose)
    assert float(jnp.linalg.norm(out_t)) < float(jnp.linalg.norm(out_l))


def test_router_z_loss_scales_with_logits():
    """z-loss penalizes large router logits (keeps the router calibrated)."""
    cfg = get_config("mixtral-8x7b", smoke=True)
    p = L.init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    p_hot = dict(p, router=p["router"] * 50.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    _, aux = moe_ffn(p, x, cfg)
    _, aux_hot = moe_ffn(p_hot, x, cfg)
    assert float(aux_hot["moe_z"]) > float(aux["moe_z"])
    # load-balance loss is O(1) for a near-uniform random router
    assert 0.5 < float(aux["moe_aux"]) < 2.0


def test_shared_experts_always_active():
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    p = L.init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    out, _ = moe_ffn(p, x, cfg)
    # zero out routed experts: output should become exactly the shared path
    p2 = dict(p)
    p2["experts"] = jax.tree.map(jnp.zeros_like, p["experts"])
    out2, _ = moe_ffn(p2, x, cfg)
    shared_only = L.mlp(p["shared"], x, "silu")
    np.testing.assert_allclose(np.asarray(out2), np.asarray(shared_only), atol=1e-5)


def test_decode_single_token_not_dropped():
    """top-k assignments of a single token always fit capacity."""
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, dispatch="dropping"))
    p = L.init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model))
    dense_cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, dispatch="dense_mix"))
    ref, _ = moe_ffn(p, x, dense_cfg)
    got, _ = moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)
