"""Right-pad prefix-safety walls for the mixed-seq-len masking contract.

``MASKABLE_BLOCKS`` admits SSM / recurrent kinds on the argument that every
cross-position mixing they do is a strictly directional (left-to-right)
scan, so zero right-padding can never reach a prefix position's output
(contract note in :mod:`repro.models.ssm`).  These tests pin that argument
empirically, at two levels:

* **module level** — the raw scan blocks (mamba, on the chunked scan and
  on the Pallas selective scan it runs on TPU, mlstm, slstm) run on a
  zero-right-padded input reproduce the exact-shape run BITWISE on the
  valid prefix; the selective scan kernel does so under arbitrary padding.
* **model level** — every smoke architecture family's DiffusionLM ``eps``
  on a padded batch with ``lengths`` set reproduces the exact-shape batch
  BITWISE on the prefix, with the pad tail exactly zero.  This is the
  property the serving engine's seq-bucketing relies on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, ssm
from repro.models.diffusion import DiffusionLM
from repro.models.layers import init_params

SMOKE_FAMILIES = [
    "llama3.2-1b",          # dense attention (control)
    "xlstm-350m",           # mlstm + slstm scans
    "hymba-1.5b",           # mamba + attention hybrid
    "deepseek-v2-lite-16b", # MLA + MoE
    "whisper-base",         # enc + xdec (causal self-attention)
]


# ---------------------------------------------------------------------------
# module level: raw directional scans
# ---------------------------------------------------------------------------


def _padded_vs_exact(fn, x, l_exact):
    """Run fn on x[:, :l_exact] and on x (right-padded with zeros); return
    both outputs as numpy."""
    exact = fn(x[:, :l_exact])
    padded = fn(x)
    return np.asarray(exact), np.asarray(padded)


def _steer_to_tpu(monkeypatch):
    """The platform says TPU, so a Mamba sequence takes the Pallas selective
    scan; with no chip here the kernel still interprets."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "interpret_mode", lambda: True)
    calls = []
    scan = ops.selective_scan
    monkeypatch.setattr(
        ops, "selective_scan", lambda *a, **kw: calls.append(1) or scan(*a, **kw)
    )
    return calls


@pytest.mark.parametrize("kind", ["mamba", "mamba-tpu", "mlstm", "slstm"])
def test_scan_blocks_prefix_bitwise(kind, monkeypatch):
    arch = {
        "mamba": "hymba-1.5b", "mamba-tpu": "hymba-1.5b",
        "mlstm": "xlstm-350m", "slstm": "xlstm-350m",
    }
    cfg = get_config(arch[kind], smoke=True)
    key = jax.random.PRNGKey(0)
    b, s, l_exact = 2, 9, 5
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model), cfg.dtype)
    x = x.at[:, l_exact:].set(0.0)  # zero right-padding
    kernel_calls = _steer_to_tpu(monkeypatch) if kind == "mamba-tpu" else None
    if kind.startswith("mamba"):
        p = init_params(ssm.mamba_specs(cfg), key, cfg.param_dtype)
        fn = lambda xi: ssm.mamba(p, xi, cfg)[0]
    elif kind == "mlstm":
        p = init_params(ssm.mlstm_specs(cfg), key, cfg.param_dtype)
        fn = lambda xi: ssm.mlstm_block(p, xi, cfg)[0]
    else:
        p = init_params(ssm.slstm_specs(cfg), key, cfg.param_dtype)
        fn = lambda xi: ssm.slstm_block(p, xi, cfg)[0]
    exact, padded = _padded_vs_exact(fn, x, l_exact)
    if kernel_calls is not None:
        assert len(kernel_calls) == 2, "mamba did not run the selective scan"
    np.testing.assert_array_equal(
        padded[:, :l_exact], exact,
        err_msg=f"{kind}: right-padding leaked into the prefix",
    )


@pytest.mark.parametrize("l_exact, s", [(5, 9), (17, 40), (250, 300)])
def test_selective_scan_kernel_prefix_bitwise(monkeypatch, l_exact, s):
    """The Pallas selective scan, as a Mamba sequence runs it on TPU: a row
    right-padded with arbitrary (nonzero) values reproduces the
    exact-length run BITWISE on its prefix.  (5, 9) runs one short time
    block against a longer one; (250, 300) spans several blocks, the exact
    run's last one padded."""
    from repro.kernels import ops

    _steer_to_tpu(monkeypatch)
    cfg = get_config("hymba-1.5b", smoke=True)
    di, n = cfg.ssm.expand * cfg.d_model, cfg.ssm.state_dim
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    dt = 0.5 * jax.nn.softplus(jax.random.normal(ks[0], (2, s, di)))
    x = jax.random.normal(ks[1], (2, s, di))
    bmat = jax.random.normal(ks[2], (2, s, n))
    c = jax.random.normal(ks[3], (2, s, n))
    a = -jnp.exp(0.5 * jax.random.normal(ks[4], (di, n)))
    h0 = jax.random.normal(ks[5], (2, di, n))
    y_exact, _ = ops.selective_scan(
        *(t[:, :l_exact] for t in (dt, x)), a,
        *(t[:, :l_exact] for t in (bmat, c)), h0,
    )
    y_pad, _ = ops.selective_scan(dt, x, a, bmat, c, h0)
    np.testing.assert_array_equal(
        np.asarray(y_pad)[:, :l_exact], np.asarray(y_exact),
        err_msg="selective scan: right-padding leaked into the prefix",
    )


def test_associative_scan_prefix_tree_is_length_stable():
    """The subtle half of the argument: jax.lax.associative_scan's combine
    tree for prefix position p must not change when the scan length grows
    (Brent–Kung — each prefix output depends only on its own index).  If a
    future jax version reshapes the tree by total length, this trips before
    any model-level wall does."""
    a = jax.random.uniform(jax.random.PRNGKey(0), (1, 16, 4), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 4), jnp.float32)
    h0 = jnp.zeros((1, 4), jnp.float32)
    for l_exact in (3, 7, 12):
        he, _ = ssm.chunked_linear_scan(
            a[:, :l_exact], b[:, :l_exact], h0, chunk=4
        )
        hp, _ = ssm.chunked_linear_scan(a, b, h0, chunk=4)
        np.testing.assert_array_equal(
            np.asarray(hp)[:, :l_exact], np.asarray(he), err_msg=str(l_exact)
        )


# ---------------------------------------------------------------------------
# model level: DiffusionLM eps on every smoke family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", SMOKE_FAMILIES)
def test_dlm_eps_prefix_bitwise(arch):
    """Padded + masked eps == exact-shape eps BITWISE on the prefix, pad
    tail exactly zero — for attention, SSM, MLA, and encoder families."""
    cfg = get_config(arch, smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    assert dlm.supports_length_masking, arch
    params = dlm.init(jax.random.PRNGKey(0))
    b, l_exact, l_pad = 2, 5, 9
    x = jax.random.normal(jax.random.PRNGKey(1), (b, l_exact, cfg.d_model))
    xp = jnp.concatenate(
        [x, jnp.zeros((b, l_pad - l_exact, cfg.d_model))], axis=1
    )
    t = jnp.float32(0.7)
    lengths = jnp.full((b,), l_exact, jnp.int32)
    e_exact = np.asarray(dlm.eps(params, x, t))
    e_exact_masked = np.asarray(dlm.eps(params, x, t, lengths=lengths))
    e_pad = np.asarray(dlm.eps(params, xp, t, lengths=lengths))
    # masking an already-exact batch is a numerical no-op (+0.0 biases)
    np.testing.assert_array_equal(e_exact_masked, e_exact, err_msg=arch)
    np.testing.assert_array_equal(
        e_pad[:, :l_exact], e_exact,
        err_msg=f"{arch}: padding changed prefix eps",
    )
    assert (e_pad[:, l_exact:] == 0.0).all(), arch


@pytest.mark.parametrize("arch", ["xlstm-350m", "deepseek-v2-lite-16b"])
def test_dlm_eps_ragged_rows_match_solo(arch):
    """Ragged per-row lengths: each valid row of a masked padded batch
    matches that row's solo exact-shape run within the documented 1e-6
    parity bar (solo runs compile separately, so bitwise isn't promised
    across program boundaries)."""
    cfg = get_config(arch, smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(jax.random.PRNGKey(0))
    lens = (3, 8, 5)
    s = max(lens)
    x = jax.random.normal(jax.random.PRNGKey(2), (len(lens), s, cfg.d_model))
    valid = jnp.arange(s)[None, :] < jnp.asarray(lens)[:, None]
    x = jnp.where(valid[..., None], x, 0.0)
    t = jnp.float32(0.4)
    e_pad = np.asarray(
        dlm.eps(params, x, t, lengths=jnp.asarray(lens, jnp.int32))
    )
    for i, L in enumerate(lens):
        solo = np.asarray(dlm.eps(params, x[i : i + 1, :L], t))[0]
        np.testing.assert_allclose(
            e_pad[i, :L], solo, atol=1e-6, err_msg=f"{arch} row={i}"
        )
        assert (e_pad[i, L:] == 0.0).all()
