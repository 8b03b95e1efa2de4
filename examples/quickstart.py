"""Quickstart: train a diffusion-LM denoiser, then sample with ERA-Solver.

End-to-end driver (deliverable b): data pipeline -> training loop ->
checkpoint -> ERA-Solver sampling -> quality report against the known data
distribution.

    PYTHONPATH=src python examples/quickstart.py                  # ~1 min CPU
    PYTHONPATH=src python examples/quickstart.py --preset 100m \
        --steps 300                                               # the real run

The ``100m`` preset is a ~100M-parameter qwen2-family denoiser — the
configuration used for the paper-style experiments on real hardware; the
default ``tiny`` preset keeps CPU runtime to about a minute.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import ERAConfig, get_solver, linear_schedule
from repro.data import DataConfig, GaussianMixtureLatents
from repro.models import build_model
from repro.models.diffusion import DiffusionLM
from repro.training import (
    OptimizerConfig,
    make_diffusion_train_step,
    train,
)

PRESETS = {
    # (base config, overrides, seq, batch)
    "tiny": ("qwen2-1.5b", dict(smoke=True), 16, 16),
    "100m": ("qwen2-1.5b", dict(), 64, 32),  # trimmed below to ~100M
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--nfe", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="artifacts/quickstart")
    args = ap.parse_args()

    base, kw, seq, batch = PRESETS[args.preset]
    cfg = get_config(base, **kw)
    if args.preset == "100m":
        cfg = cfg.with_(
            num_layers=10, d_model=768, num_heads=12, num_kv_heads=4,
            d_ff=2048, vocab_size=4096, vocab_pad_multiple=64, head_dim=64,
            dtype=jnp.float32, remat=False,
        )
    dlm = DiffusionLM(build_model(cfg))
    params = dlm.init(jax.random.PRNGKey(args.seed))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"denoiser: {cfg.name} ({n/1e6:.1f}M params), seq={seq}")

    sched = linear_schedule()
    dc = DataConfig(vocab_size=1, seq_len=seq, batch_size=batch,
                    kind="diffusion", d_model=cfg.d_model, num_modes=4,
                    seed=args.seed)
    data = GaussianMixtureLatents(dc)
    step = make_diffusion_train_step(
        dlm,
        OptimizerConfig(lr=2e-3, warmup_steps=max(args.steps // 20, 5),
                        total_steps=args.steps),
        sched,
    )
    res = train(step, params, data.batches(), args.steps,
                ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 2, 50))
    print(f"trained: loss {res.history[0]['loss']:.4f} -> "
          f"{res.history[-1]['loss']:.4f}")

    # --- sample with ERA-Solver (the paper's Algorithm 1) ---
    xT = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                           (64, seq, cfg.d_model))
    out = get_solver("era")(
        dlm.eps_fn(res.params), xT, sched,
        ERAConfig(nfe=args.nfe, k=3, lam=5.0, error_norm="mean"),
    )
    mu, var = data.moments()
    got = np.asarray(out.x0.reshape(-1, cfg.d_model))
    mu_err = float(np.linalg.norm(got.mean(0) - mu) / np.linalg.norm(mu))
    var_err = float(np.linalg.norm(got.var(0) - var) / np.linalg.norm(var))
    print(f"ERA-Solver @ NFE={args.nfe}: mean-err {mu_err:.3f}, "
          f"var-err {var_err:.3f} (vs data moments)")
    print("delta_eps history: "
          f"{np.asarray(out.aux['delta_eps_history'])[3:].round(3).tolist()}")

    # --- the same model behind the batched serving engine ---
    from repro.serving import (
        AsyncBatchedSampler, EngineConfig, SampleRequest, build_engine,
    )

    engine = build_engine(dlm, sched, EngineConfig(batch_buckets=(1, 8)))
    queue = AsyncBatchedSampler(engine, res.params)
    futs = [
        queue.submit(SampleRequest(batch=1, seq_len=seq, nfe=args.nfe, seed=s))
        for s in range(4)
    ]
    queue.flush()
    results = [f.result() for f in futs]
    lat = sum(r.latency_s for r in results) / len(results)
    print(f"batched engine: {len(results)} requests fused to "
          f"batch {results[0].padded_batch}, "
          f"mean latency {lat * 1e3:.1f} ms "
          f"({len(engine.compile_cache())} compiled bucket)")


if __name__ == "__main__":
    main()
