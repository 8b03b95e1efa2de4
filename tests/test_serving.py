import sys

import jax
import jax.numpy as jnp
import numpy as np

from conftest import run_flush
from repro.configs import get_config
from repro.core import ERAConfig, linear_schedule, sample_program
from repro.models import build_model
from repro.models.diffusion import DiffusionLM
from repro.serving import (
    Engine,
    EngineConfig,
    SampleRequest,
    ServeConfig,
    build_engine,
    cache_slots,
    resolve_window,
    result_keys as K,
)

KEY = jax.random.PRNGKey(0)


def test_generate_basic():
    cfg = get_config("llama3.2-1b", smoke=True)
    m = build_model(cfg)
    eng = Engine(m, ServeConfig(max_len=128))
    params = m.init(KEY)
    prompts = jax.random.randint(KEY, (2, 10), 0, cfg.vocab_size)
    toks = eng.generate(params, prompts, 6)
    assert toks.shape == (2, 6)
    assert int(jnp.max(toks)) < cfg.vocab_size


def test_greedy_deterministic():
    cfg = get_config("qwen2-1.5b", smoke=True)
    m = build_model(cfg)
    eng = Engine(m, ServeConfig(max_len=64))
    params = m.init(KEY)
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    a = eng.generate(params, prompts, 5, key=jax.random.PRNGKey(1))
    b = eng.generate(params, prompts, 5, key=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_windowed_decode_matches_full_within_window():
    """With prompt+gen <= window, ring-buffer decode == full attention."""
    cfg = get_config("llama3.2-1b", smoke=True)
    m = build_model(cfg)
    params = m.init(KEY)
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    full = Engine(m, ServeConfig(max_len=64)).generate(params, prompts, 6)
    ring = Engine(m, ServeConfig(max_len=64, window_override=32)).generate(
        params, prompts, 6
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(ring))


def test_long_decode_beyond_window_runs():
    cfg = get_config("minitron-4b", smoke=True)
    m = build_model(cfg)
    params = m.init(KEY)
    eng = Engine(m, ServeConfig(max_len=512, window_override=16))
    prompts = jax.random.randint(KEY, (1, 48), 0, cfg.vocab_size)
    toks = eng.generate(params, prompts, 40)  # far beyond the 16-slot ring
    assert toks.shape == (1, 40)


def test_cache_slots_policy():
    cfg = get_config("mixtral-8x7b")          # native SWA 4096
    assert cache_slots(cfg, ServeConfig(max_len=100000)) == 4096
    dense = get_config("deepseek-67b")
    assert cache_slots(dense, ServeConfig(max_len=4096)) == 4096
    assert resolve_window(dense, ServeConfig(), 524288) == dense.long_context_window
    assert resolve_window(cfg, ServeConfig(), 4096) == -1


def test_engine_generate_on_mesh_matches_single_device(mesh8):
    """Data-parallel generate (params replicated, batch sharded) is token-
    identical to the single-device engine; a non-divisible batch silently
    degrades to replicated."""
    cfg = get_config("llama3.2-1b", smoke=True)
    m = build_model(cfg)
    params = m.init(KEY)
    prompts = jax.random.randint(KEY, (8, 10), 0, cfg.vocab_size)
    single = Engine(m, ServeConfig(max_len=64)).generate(params, prompts, 4)
    meshed = Engine(m, ServeConfig(max_len=64), mesh=mesh8)
    toks = meshed.generate(params, prompts, 4)
    np.testing.assert_array_equal(np.asarray(single), np.asarray(toks))
    toks3 = meshed.generate(params, prompts[:3], 4)
    np.testing.assert_array_equal(np.asarray(single)[:3], np.asarray(toks3))


def _one_shot_engine(dlm, solver="era"):
    """The one-shot CLI mode's engine: exact-size, the paper's shared-delta
    ERA (k=3 at NFE 6) or the solver's registry default."""
    cfg = EngineConfig(
        solver=solver, nfe=6, k=3, per_sample=False, batch_buckets=None
    )
    return build_engine(dlm, linear_schedule(), cfg)


def test_sampler_service_solver_choice():
    cfg = get_config("qwen2-1.5b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(KEY)
    outs = {}
    for solver in ("ddim", "era"):
        (res,) = run_flush(
            _one_shot_engine(dlm, solver),
            [SampleRequest(batch=2, seq_len=8, nfe=6)],
            params,
        )
        x0 = res.x0
        assert x0.shape == (2, 8, cfg.d_model)
        assert not bool(jnp.any(jnp.isnan(x0)))
        outs[solver] = np.asarray(x0)
    assert np.max(np.abs(outs["ddim"] - outs["era"])) > 1e-6  # different paths


def test_sampler_service_surfaces_engine_telemetry():
    """A result's info dict carries the engine telemetry alongside the
    solver aux: latency_s and padded_batch, not just wall_s."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(KEY)
    (res,) = run_flush(
        _one_shot_engine(dlm), [SampleRequest(batch=2, seq_len=8, nfe=6)], params
    )
    info = res.info
    assert info[K.PADDED_BATCH] == 2  # exact-size buckets
    assert info[K.LATENCY_S] >= info[K.WALL_S] > 0
    assert K.DELTA_EPS_HISTORY in info


def test_sample_program_lowerable():
    """The whole ERA sampling loop lowers as one XLA program:
    ``sample_program``, the program ``launch/dryrun.py`` lowers."""
    cfg = get_config("llama3.2-1b", smoke=True)
    dlm = DiffusionLM(build_model(cfg))
    prog = sample_program(dlm, linear_schedule(), "era", ERAConfig(nfe=6, k=3))
    aparams = dlm.init_abstract()
    x = jax.ShapeDtypeStruct((2, 8, cfg.d_model), jnp.float32)
    lowered = jax.jit(prog).lower(aparams, x)
    assert lowered.compile() is not None


def test_serve_cli_one_shot_diffusion(monkeypatch, capsys):
    """``launch/serve.py --mode diffusion`` (no --continuous / --listen)
    builds the engine, submits one request and flushes it on its own
    thread."""
    from repro.launch import serve

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen2-1.5b", "--smoke", "--mode", "diffusion",
        "--nfe", "6", "--k", "3", "--batch", "2", "--seq", "8",
    ])
    serve.main()
    out = capsys.readouterr().out
    d_model = get_config("qwen2-1.5b", smoke=True).d_model
    assert f"sampled latents (2, 8, {d_model}) via era nfe=6" in out


def test_serve_cli_continuous_diffusion(monkeypatch, capsys):
    """``launch/serve.py --mode diffusion --continuous`` drives an
    open-loop Poisson stream (``open_loop``) through the scheduler's
    drain thread and reports its latency percentiles."""
    from repro.launch import serve

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen2-1.5b", "--smoke", "--mode", "diffusion",
        "--continuous", "--requests", "4", "--rate", "100", "--no-warm",
        "--nfe", "6", "--k", "3", "--seq", "8", "--batch-buckets", "1,4",
    ])
    serve.main()
    out = capsys.readouterr().out
    assert "continuous[era]: 4 req @ 100.0/s" in out
    assert "p50=" in out and "batches=" in out
