"""Serving launcher: AR generation or ERA-Solver diffusion sampling.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --mode ar --batch 4 --prompt-len 16 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --mode diffusion --solver era --nfe 10
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --mode diffusion --continuous --requests 16 --rate 20
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --mode diffusion --listen --port 0
    PYTHONPATH=src python -m repro.launch.serve \
        --mode diffusion --connect http://127.0.0.1:8752 --requests 4

``--continuous`` drives the continuous-batching scheduler with a simulated
open-loop client: ``--requests`` single-sample requests arrive with Poisson
gaps at ``--rate`` req/s (open-loop — arrivals never wait for service), and
the run reports p50/p99 arrival-to-result latency, throughput, and how full
the fused batches ran.

``--listen`` runs the HTTP front door (``POST /v1/sample``, ``GET
/metrics``, ``GET /healthz`` liveness, ``GET /readyz`` readiness — see
docs/serving.md) over the same engine and scheduler; once the socket is
bound it prints the machine-parsable ready line ``FRONTDOOR READY <url>``
(``--port 0`` binds an ephemeral port) and serves until interrupted.  The
AOT warmup grid compiles on a background thread behind ``/readyz``
(``--no-warm`` to skip; ``--compile-cache`` turns redeploy warmups into
disk loads from ``$JAX_COMPILATION_CACHE_DIR``, or the checkout's
``.jax_cache`` when that is unset).  ``--connect URL`` is the matching
wire client: it needs no model or params, just the server's URL.

Every diffusion mode builds its engine through
:func:`repro.serving.build_engine` and queues its requests on
:class:`repro.serving.AsyncBatchedSampler` — the one-shot mode (submit,
then ``flush()``), the continuous simulator, and the HTTP server run the
same path, so a result observed over the wire is the result the
in-process paths produce.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import arch_names, get_config
from repro.core import linear_schedule, solver_names
from repro.data import frontend_features
from repro.models import build_model
from repro.models.diffusion import DiffusionLM
from repro.serving import (
    AsyncBatchedSampler,
    Engine,
    EngineConfig,
    FrontDoorClient,
    SampleRequest,
    SchedulerPolicy,
    ServeConfig,
    build_engine,
    open_loop,
    result_keys as K,
    serve_frontdoor,
    warmup_kwargs,
)


def _engine_config(
    args, per_sample: bool, fused: bool,
    warmup_seq_lens: tuple[int, ...] | None = None,
) -> EngineConfig:
    """CLI args -> the one EngineConfig every diffusion mode builds from.
    ``fused`` engines get the serving bucket ladder; the one-shot mode
    runs exact-size (no fusion).  ``warmup_seq_lens`` names the exact
    lengths the AOT warmup grid covers when the engine has no seq-bucket
    ladder (each mode passes the lengths its traffic will use)."""
    seq_buckets = (
        tuple(int(x) for x in args.seq_buckets.split(","))
        if args.seq_buckets
        else None
    )
    nfe_buckets = (
        tuple(int(x) for x in args.nfe_buckets.split(","))
        if args.nfe_buckets
        else None
    )
    batch_buckets = tuple(int(x) for x in args.batch_buckets.split(","))
    return EngineConfig(
        solver=args.solver,
        nfe=args.nfe,
        k=args.k,
        lam=args.lam,
        per_sample=per_sample,
        batch_buckets=batch_buckets if fused else None,
        seq_buckets=seq_buckets if fused else None,
        nfe_buckets=nfe_buckets if fused else None,
        warmup="grid" if (fused and args.warm) else "none",
        warmup_nfes=(
            tuple(int(x) for x in args.warmup_nfes.split(","))
            if args.warmup_nfes
            else None
        ),
        warmup_seq_lens=warmup_seq_lens if fused else None,
        compile_cache=args.compile_cache,
    )


def _warm_engine(engine, params, cfg: EngineConfig, mix) -> None:
    """AOT-compile the engine's program grid for every solver in ``mix``
    (no sampling — abstract shapes only; see ``FusedExecutor.warmup``)."""
    kw = warmup_kwargs(cfg)
    if kw is None:
        return
    rep = engine.warmup(params, solvers=tuple(mix), **kw)
    print(
        f"warmup: {rep['programs']} programs in {rep['wall_s']:.2f}s "
        f"({rep['fresh']} fresh, {rep['disk']} from compile cache)",
        flush=True,
    )


def run_continuous(dlm, params, args) -> None:
    """Open-loop Poisson client against the continuous-batching scheduler.

    With ``--mix solver_a,solver_b,...`` the stream cycles requests through
    several registry solvers — each request routes to its own solver's
    program inside one engine (per-(solver, seq, nfe) fuse queues).  With
    ``--seq-buckets`` + ``--seq-mix-lens``, requests of different lengths
    fuse into shared length-masked batches; with ``--nfe-buckets`` +
    ``--nfe-mix-nfes``, requests of different step budgets fuse into
    shared step-masked batches (see docs/serving.md)."""
    mix = [s.strip() for s in args.mix.split(",")] if args.mix else [args.solver]
    lens = (
        [int(x) for x in args.seq_mix_lens.split(",")]
        if args.seq_mix_lens
        else [args.seq]
    )
    nfes = (
        [int(x) for x in args.nfe_mix_nfes.split(",")]
        if args.nfe_mix_nfes
        else [args.nfe]
    )
    cfg = _engine_config(
        args, per_sample=True, fused=True, warmup_seq_lens=tuple(lens)
    )
    engine = build_engine(dlm, linear_schedule(), cfg)
    _warm_engine(engine, params, cfg, mix)

    policy = SchedulerPolicy(
        max_wait_ms=args.max_wait_ms, target_occupancy=args.occupancy
    )
    rng = np.random.default_rng(args.seed)
    gaps = rng.exponential(1.0 / args.rate, args.requests)
    futures = []
    with AsyncBatchedSampler(engine, params, policy) as sched:
        t_start = open_loop(
            gaps,
            lambda i: futures.append(
                sched.submit(
                    SampleRequest(
                        batch=1, seq_len=lens[i % len(lens)],
                        nfe=nfes[i % len(nfes)],
                        solver=mix[i % len(mix)], seed=args.seed + i,
                    )
                )
            ),
        )
        results = [f.result() for f in futures]
        makespan = time.perf_counter() - t_start
        stats = sched.stats()
    lats_ms = np.array([r.latency_s for r in results]) * 1e3
    print(
        f"continuous[{','.join(mix)}]: {args.requests} req @ {args.rate:.1f}/s "
        f"(max_wait={policy.max_wait_ms}ms occ={policy.target_occupancy}) | "
        f"p50={np.percentile(lats_ms, 50):.1f}ms "
        f"p99={np.percentile(lats_ms, 99):.1f}ms "
        f"thpt={args.requests / makespan:.1f}/s "
        f"batches={stats[K.BATCHES]} "
        f"mean_rows={stats[K.MEAN_BATCH_ROWS]:.1f}"
    )


def run_listen(dlm, params, args) -> None:
    """HTTP front-door server: bind, print the ready line, serve until
    interrupted.  The AOT warmup grid (default solver × batch buckets ×
    seq buckets × nfe) compiles on a background thread — the listener is
    up immediately, and ``GET /readyz`` flips 503 -> 200 once the grid is
    in (``--no-warm`` skips it: ready at bind, first requests compile)."""
    cfg = _engine_config(
        args, per_sample=True, fused=True, warmup_seq_lens=(args.seq,)
    )
    engine = build_engine(dlm, linear_schedule(), cfg)
    policy = SchedulerPolicy(
        max_wait_ms=args.max_wait_ms,
        target_occupancy=args.occupancy,
        max_queue_rows=(
            args.max_queue_rows if args.max_queue_rows > 0 else None
        ),
    )
    kw = warmup_kwargs(cfg)
    door = serve_frontdoor(
        engine, params, policy, host=args.host, port=args.port,
        warmup=(
            {**kw, "solvers": (args.solver,)} if kw is not None else None
        ),
    )
    # machine-parsable sentinel: tests and scripts wait for this
    # line before opening the client (bind != ready — poll /readyz for
    # the end of the compile wall)
    print(f"FRONTDOOR READY {door.url}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        door.stop()


def run_connect(args) -> None:
    """Wire client: sample over HTTP against a running ``--listen``
    server.  Needs no local model — the request is pure schema."""
    client = FrontDoorClient(args.connect, timeout=args.timeout)
    lats_ms = []
    for i in range(args.requests):
        t0 = time.perf_counter()
        res = client.sample(
            SampleRequest(
                batch=args.batch, seq_len=args.seq, nfe=args.nfe,
                solver=args.solver, seed=args.seed + i,
            )
        )
        lats_ms.append((time.perf_counter() - t0) * 1e3)
        x0 = res.x0
        print(
            f"req[{i}] x0 {x0.shape} via {args.solver} nfe={args.nfe} | "
            f"wire={lats_ms[-1]:.1f}ms engine_wall={res.info[K.WALL_S]:.2f}s "
            f"(mean {float(np.mean(x0)):+.4f}, std {float(np.std(x0)):.4f})"
        )
    print(
        f"connect: {args.requests} req | "
        f"p50={np.percentile(lats_ms, 50):.1f}ms "
        f"p99={np.percentile(lats_ms, 99):.1f}ms"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=arch_names())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=["ar", "diffusion"], default="ar")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--window", type=int, default=-1)
    ap.add_argument("--solver", default="era", choices=solver_names())
    ap.add_argument("--nfe", type=int, default=10)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--lam", type=float, default=5.0)
    ap.add_argument("--seq", type=int, default=32, help="diffusion seq len")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--continuous",
        action="store_true",
        help="serve a simulated open-loop Poisson stream through the "
        "continuous-batching scheduler (diffusion mode only)",
    )
    ap.add_argument(
        "--listen",
        action="store_true",
        help="run the HTTP front door over the continuous-batching "
        "scheduler (diffusion mode only); prints 'FRONTDOOR READY <url>' "
        "once bound",
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument(
        "--port", type=int, default=0,
        help="--listen port (0 = ephemeral, reported in the ready line)",
    )
    ap.add_argument(
        "--connect",
        default=None,
        metavar="URL",
        help="act as a wire client against a running --listen server "
        "(diffusion mode only; no local model needed)",
    )
    ap.add_argument(
        "--timeout", type=float, default=None,
        help="--connect per-request socket timeout in seconds",
    )
    ap.add_argument(
        "--max-queue-rows", type=int, default=4096,
        help="--listen admission bound per fuse-group queue (HTTP 429 "
        "past it; default 4096, <= 0 for unbounded)",
    )
    ap.add_argument(
        "--no-warm", dest="warm", action="store_false",
        help="skip the AOT warmup grid compile (--listen boots ready "
        "immediately; first requests pay their own compiles)",
    )
    ap.add_argument(
        "--warmup-nfes",
        default=None,
        help="comma-separated NFE list the AOT warmup grid covers "
        "(default: --nfe only)",
    )
    ap.add_argument(
        "--compile-cache",
        action="store_true",
        help="persistent XLA compilation cache in $JAX_COMPILATION_CACHE_DIR "
        "(else the checkout's .jax_cache): warmup on a redeployed replica "
        "loads yesterday's programs from disk instead of recompiling",
    )
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument(
        "--mix",
        default=None,
        help="comma-separated registry solvers to cycle the --continuous "
        "stream through (per-request routing in one engine), e.g. "
        "'era,ddim,dpm_solver_pp2m'",
    )
    ap.add_argument("--rate", type=float, default=20.0, help="arrivals/s")
    ap.add_argument(
        "--batch-buckets",
        default="1,8,64",
        help="comma-separated batch-shape ladder for the fused "
        "(--continuous/--listen) engine",
    )
    ap.add_argument(
        "--seq-buckets",
        default=None,
        help="comma-separated seq-bucket ladder for the --continuous "
        "engine (mixed-seq-len fusion with padding masks), e.g. '32,64'",
    )
    ap.add_argument(
        "--seq-mix-lens",
        default=None,
        help="comma-separated seq_lens the --continuous stream cycles "
        "through (default: --seq only)",
    )
    ap.add_argument(
        "--nfe-buckets",
        default=None,
        help="comma-separated NFE-bucket ladder for the fused "
        "(--continuous/--listen) engine (mixed-NFE fusion with per-row "
        "step masks; requests above the top bucket are rejected), e.g. "
        "'12,25'",
    )
    ap.add_argument(
        "--nfe-mix-nfes",
        default=None,
        help="comma-separated NFE budgets the --continuous stream cycles "
        "through (default: --nfe only)",
    )
    ap.add_argument("--max-wait-ms", type=float, default=25.0)
    ap.add_argument(
        "--occupancy", type=float, default=1.0,
        help="launch a batch early once this fraction of the largest "
        "bucket is pending",
    )
    args = ap.parse_args()
    if (args.continuous or args.listen or args.connect) and args.mode != "diffusion":
        ap.error("--continuous/--listen/--connect require --mode diffusion")
    if args.connect:
        run_connect(args)
        return

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)

    if args.mode == "diffusion":
        dlm = DiffusionLM(model)
        params = dlm.init(key)
        if args.listen:
            run_listen(dlm, params, args)
            return
        if args.continuous:
            run_continuous(dlm, params, args)
            return
        engine = build_engine(
            dlm,
            linear_schedule(),
            _engine_config(args, per_sample=False, fused=False),
        )
        sched = AsyncBatchedSampler(engine, params)
        fut = sched.submit(SampleRequest(
            batch=args.batch, seq_len=args.seq, nfe=args.nfe, seed=args.seed
        ))
        sched.flush()
        res = fut.result()
        x0 = res.x0
        print(
            f"sampled latents {x0.shape} via {args.solver} nfe={args.nfe} "
            f"in {res.info[K.WALL_S]:.2f}s "
            f"(mean {float(jnp.mean(x0)):+.4f}, std {float(jnp.std(x0)):.4f})"
        )
        return

    params = model.init(key)
    eng = Engine(model, ServeConfig(max_len=args.max_len, window_override=args.window))
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = jnp.asarray(
            frontend_features(rng, args.batch, cfg.frontend.num_positions, cfg.d_model)
        )
    if cfg.family == "audio":
        extras["frames"] = jnp.asarray(
            frontend_features(rng, args.batch, cfg.frontend.num_positions, cfg.d_model)
        )
    t0 = time.perf_counter()
    toks = eng.generate(params, prompts, args.gen, extras=extras, key=key)
    toks = jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    print(
        f"generated {toks.shape} in {dt:.2f}s "
        f"({args.batch * args.gen / dt:.1f} tok/s); first row: "
        f"{toks[0][:10].tolist()}"
    )


if __name__ == "__main__":
    main()
