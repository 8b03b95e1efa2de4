"""DeepSeek-V2-Lite on one expert-parallel rank: the program's MLA and
dropless held-experts MoE against the benchmark's plain float32 reference
(``bench/layers/mla_moe.py``, ``mla_dense.py``), YaRN against the published
formula, the share test of the expert cut, and dropless invariance.

Everything runs the smoke preset of ``deepseek-v2-lite-16b-ep8`` (2 layers,
d=128, 4 experts top-2 of which 2 are held, 1 shared) in float32 on the
CPU, with the weights the benchmark draws."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models.blocks import BLOCKS, BlockCtx
from repro.models.diffusion import DiffusionLM
from repro.models.moe import moe_ffn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import loader, reference, weights  # noqa: E402

NAME = "deepseek-v2-lite-16b-ep8"
SEED = 2**31 + 77

#: the program and the reference both compute in float32 on the CPU; they
#: differ only in the order of their sums (and the program scales q by the
#: softmax gain before its scores, the reference its scores), so a few
#: float32 ulps of the layer's output scale
RTOL = 2e-5


@pytest.fixture(autouse=True)
def _fresh_layer_modules():
    """Each test loads the layer modules anew and leaves none cached, so
    that no other test of the process finds a kind it did not load."""
    loader.layer.cache_clear()
    yield
    loader.layer.cache_clear()


def _bench_cfg():
    f = loader.config("deepseek-v2-lite")
    return dict(f, **f["smoke"])


@pytest.fixture(scope="module")
def model():
    cfg = get_config(NAME, smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = weights.make_weights(dlm.init_abstract(), SEED, 0.01)
    return cfg, dlm, params


def _layer(params, seg):
    return jax.tree.map(lambda a: a[0], params["backbone"]["segs"][seg])


def _x(shape, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= rtol, err


# ---- the program's layers against the reference ---------------------------


@pytest.mark.parametrize("seg", ["0_mla_dense", "1_mla_moe"])
def test_block_matches_reference(seg, model):
    cfg, _, params = model
    kind = seg.split("_", 1)[1]
    p = _layer(params, seg)
    x = _x((2, 24, cfg.d_model))
    got, _, _ = BLOCKS[kind].apply(p, x, None, BlockCtx(mode="train", causal=False), cfg)
    want = loader.layer(kind).reference(p, x, _bench_cfg(), "f32")
    _close(got, want)


def test_eps_matches_reference(model):
    """The whole denoiser.  Compared less x_t, the head's own output: at
    gain 0.01 it is about 100x smaller than the residual it carries, so its
    relative error is the blocks' error grown by that factor (5e-6 read)."""
    cfg, dlm, params = model
    x = _x((2, 24, cfg.d_model), seed=3)
    got = dlm.eps(params, x, jnp.float32(0.37))
    want = reference.eps(params, x, jnp.float32(0.37), _bench_cfg())
    _close(got - x, want - x, rtol=1e-4)


def test_block_is_bidirectional(model):
    """The denoiser's MLA sees later positions: changing the last position
    moves the first one's output (a causal mask would not)."""
    cfg, _, params = model
    p = _layer(params, "0_mla_dense")
    x = _x((1, 12, cfg.d_model))
    ctx = BlockCtx(mode="train", causal=False)
    a, _, _ = BLOCKS["mla_dense"].apply(p, x, None, ctx, cfg)
    b, _, _ = BLOCKS["mla_dense"].apply(p, x.at[:, -1].set(0.0), None, ctx, cfg)
    assert float(jnp.max(jnp.abs(a[:, 0] - b[:, 0]))) > 1e-4


# ---- YaRN -------------------------------------------------------------------


def test_yarn_frequencies_and_mscale():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4096 positions, beta_fast 32,
    beta_slow 1, 64 rope dims, theta 1e4): the correction dims are
    floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4)) = floor(10.47) = 10 and
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = ceil(22.51) = 23; pair i keeps
    the plain frequency 1e4^(-2i/64) up to 10, takes it over 40 from 23, and
    blends the two on the ramp (i - 10) / 13 between.  The softmax gain is
    (0.1 * 0.707 * ln 40 + 1)^2."""
    a = get_config("deepseek-v2-lite-16b").mla
    got = MLA.rope_inv_freq(a, 1e4)
    plain = [1e4 ** (-2 * i / 64) for i in range(32)]
    want = []
    for i, f in enumerate(plain):
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        want.append(f / 40 * ramp + f * (1 - ramp))
    np.testing.assert_allclose(got, np.float32(want), rtol=1e-6)
    assert got[10] == np.float32(plain[10]) and got[23] == np.float32(plain[23] / 40)
    np.testing.assert_allclose(got[16], (plain[16] / 40) * 6 / 13 + plain[16] * 7 / 13,
                               rtol=1e-6)
    assert MLA.softmax_gain(a) == pytest.approx(1.5896261651, abs=1e-9)
    assert (0.1 * 0.707 * math.log(40) + 1) ** 2 == pytest.approx(MLA.softmax_gain(a))
    # the reference's YaRN, written separately, agrees
    inv_freq, scale, amplitude = loader.layer("mla_moe").yarn(loader.config("deepseek-v2-lite"))
    np.testing.assert_allclose(inv_freq, got, rtol=1e-7)
    assert scale == pytest.approx(MLA.softmax_gain(a) / math.sqrt(192))
    assert amplitude == 1.0


def test_plain_rope_without_yarn():
    """rope_factor 1 gives the plain frequencies and no softmax gain."""
    a = dataclasses.replace(get_config("deepseek-v2-lite-16b").mla, rope_factor=1.0,
                            rope_mscale_all_dim=0.0)
    np.testing.assert_allclose(MLA.rope_inv_freq(a, 1e4),
                               [1e4 ** (-2 * i / 64) for i in range(32)], rtol=1e-6)
    assert MLA.softmax_gain(a) == 1.0


# ---- the expert cut ---------------------------------------------------------


def _moe_cfg(cfg, first, held, shared=True):
    m = dataclasses.replace(cfg.moe, first_expert=first, experts_held=held,
                            num_shared=cfg.moe.num_shared if shared else 0)
    return cfg.with_(moe=m)


@pytest.mark.parametrize("shares", [[(0, 2), (2, 2)], [(0, 1), (1, 1), (2, 1), (3, 1)],
                                    [(0, 3), (3, 1)]], ids=["2x2", "4x1", "3+1"])
def test_shares_add_up_to_the_uncut_layer(shares):
    """model-configs §4: the routed parts that the shares compute (each
    share with its held experts only), with the shared experts counted once,
    add up to the uncut reference layer, which holds all four experts."""
    full = get_config("deepseek-v2-lite-16b", smoke=True)
    p = weights.make_weights(DiffusionLM(build_model(full)).init_abstract(), SEED, 0.01)
    p = _layer(p, "1_mla_moe")["moe"]
    x = _x((2, 24, full.d_model), seed=5)
    total = 0.0
    for first, held in shares:
        part = dict(p, experts=jax.tree.map(lambda w: w[first:first + held], p["experts"]))
        part.pop("shared")
        out, _ = moe_ffn(part, x, _moe_cfg(full, first, held, shared=False))
        total = total + out
    total = total + L.mlp(p["shared"], x, "silu")
    uncut = dict(_bench_cfg(), n_routed_experts=4, first_routed_expert_held=0)
    want = loader.layer("mla_moe").moe(p, x, uncut, "f32")
    _close(total, want)
    got, _ = moe_ffn(p, x, full)
    _close(got, want)


def test_held_share_matches_reference(model):
    """The held share alone (experts 0-1 of 4, as the ep8 preset holds) is
    the reference's share: routing over all four, computing two."""
    cfg, _, params = model
    p = _layer(params, "1_mla_moe")["moe"]
    assert p["experts"]["wi"].shape[0] == 2 and p["router"].shape[1] == 4
    x = _x((2, 24, cfg.d_model), seed=7)
    got, _ = moe_ffn(p, x, cfg)
    _close(got, loader.layer("mla_moe").moe(p, x, _bench_cfg(), "f32"))


# ---- dropless ---------------------------------------------------------------


def test_dropless_matches_dense_mix(model):
    """The ragged products give what every held expert on every token,
    weighted by its routing weight, gives (``dense_mix``)."""
    cfg, _, params = model
    p = _layer(params, "1_mla_moe")["moe"]
    x = _x((3, 20, cfg.d_model), seed=9)
    got, _ = moe_ffn(p, x, cfg)
    dense = cfg.with_(moe=dataclasses.replace(cfg.moe, dispatch="dense_mix"))
    want, _ = moe_ffn(p, x, dense)
    _close(got, want)


@pytest.mark.parametrize("where", ["batch-mates", "padding"])
def test_dropless_output_ignores_batch_mates(where, model):
    """A token's output depends on its own routing alone: other rows, or a
    longer padded tail, leave row 0's first 16 positions as they were (every
    assignment runs; no capacity to compete for)."""
    cfg, _, params = model
    p = _layer(params, "1_mla_moe")["moe"]
    x = _x((2, 16, cfg.d_model), seed=11)
    base, _ = moe_ffn(p, x, cfg)
    if where == "batch-mates":
        other = x.at[1].set(_x((16, cfg.d_model), seed=12) * 3.0)
    else:
        other = jnp.concatenate([x, _x((2, 48, cfg.d_model), seed=13)], axis=1)
    got, _ = moe_ffn(p, other, cfg)
    _close(got[0, :16], base[0], rtol=1e-6)


def test_dropping_does_depend_on_batch_mates(model):
    """The contrast: at a tight capacity the dropping dispatch changes a
    token's output when its batch-mates crowd its experts."""
    cfg, _, params = model
    full = get_config("deepseek-v2-lite-16b", smoke=True)
    drop = full.with_(moe=dataclasses.replace(full.moe, dispatch="dropping",
                                              capacity_factor=0.5))
    p = weights.make_weights(DiffusionLM(build_model(full)).init_abstract(), SEED, 0.01)
    p = _layer(p, "1_mla_moe")["moe"]
    x = _x((1, 16, full.d_model), seed=11)
    crowd = jnp.concatenate([x, jnp.broadcast_to(x[:, :1], (1, 48, full.d_model))], axis=1)
    a, _ = moe_ffn(p, x, drop)
    b, _ = moe_ffn(p, crowd, drop)
    assert float(jnp.max(jnp.abs(a[0] - b[0, :16]))) > 1e-3
