"""One engine-construction path for every serve mode.

``launch/serve.py`` has three ways to stand up a sampling engine (one-shot,
continuous scheduler, and the HTTP front door), each hand-assembling
solver configs and bucket ladders.  This module is the single factory they
all go through: an :class:`EngineConfig` captures every engine-shape
decision as one frozen, hashable value, and :func:`build_engine` turns it
into a :class:`~repro.serving.executor.FusedExecutor`.  The HTTP
server, the ``--continuous`` simulator, and the one-shot mode therefore
serve *the same engine* — same solver config, same fuse buckets, same
compile-cache shape — so a result observed over the wire is the result the
in-process paths produce.
"""

from __future__ import annotations

import dataclasses

from repro.core import (
    ERAConfig,
    NoiseSchedule,
    SolverConfig,
    default_config,
)
from repro.models.diffusion import DiffusionLM
from repro.serving.compile_cache import configure_persistent_cache
from repro.serving.executor import (
    DEFAULT_MAX_BATCH,
    DEFAULT_MAX_NFE,
    DEFAULT_MAX_SEQ_LEN,
    FusedExecutor,
)
from repro.serving.metrics import MetricsRegistry

#: legal values of :attr:`EngineConfig.warmup`
WARMUP_MODES = ("none", "grid")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything that shapes a serving engine, in one frozen value.

    * ``solver`` / ``nfe`` — the default solver program and its step count
      (per-request ``SampleRequest.solver`` routing still works on top).
    * ``k`` / ``lam`` — ERA Lagrange order and error-robust selection
      weight (ignored by non-ERA solvers, which take their registry
      defaults at this ``nfe``).
    * ``per_sample`` — per-sample ERS (the serving default: keeps every
      row of a fused batch independent).  ``False`` = the paper's shared
      scalar delta_eps, which couples a batch, so the engine serves such
      configs one exact-size request at a time.
    * ``batch_buckets`` — compiled batch-shape ladder (``None`` =
      exact-size, no fusion — the one-shot mode's shape).
    * ``seq_buckets`` — opt-in mixed-seq-len fusion ladder (``None`` =
      exact seq_len per fuse group).
    * ``nfe_buckets`` — opt-in mixed-NFE fusion ladder (``None`` = exact
      nfe per fuse group): requests whose ``nfe`` differ share one
      compiled program that scans to the bucketed max step count under
      per-row step masks, and the warmup grid / jit cache are bounded by
      the ladder instead of by distinct request NFEs.  Requests above the
      top bucket are rejected at submit, like the seq ladder.
    * ``max_batch`` / ``max_nfe`` / ``max_seq_len`` — per-request resource
      ceilings enforced at submit (HTTP 400 at the front door): a single
      wire request must not be able to force a multi-GB allocation or a
      pathological compile after admission.  ``None`` = unbounded
      (trusted in-process callers); ``max_seq_len`` applies only when no
      ``seq_buckets`` ladder already bounds the sequence axis.
    * ``warmup`` — cold-start policy: ``"grid"`` = callers should AOT
      pre-compile the configured program grid at boot
      (:meth:`~repro.serving.executor.FusedExecutor.warmup` with
      :func:`warmup_kwargs`); ``"none"`` = programs compile lazily at
      first request.  ``warmup_nfes`` / ``warmup_seq_lens`` extend the
      grid beyond the defaults (the config's ``nfe``; the seq-bucket
      ladder, or — for exact-seq-len traffic — the lengths callers expect
      to serve).
    * ``compile_cache`` — turn on the persistent XLA compilation cache
      (process-global): a redeployed replica's warmup becomes disk loads
      instead of fresh compiles.  Where it lives is not an engine option:
      ``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout
      ``.jax_cache`` (:func:`~repro.serving.compile_cache.cache_dir`).
    """

    solver: str = "era"
    nfe: int = 10
    k: int = 4
    lam: float = 5.0
    per_sample: bool = True
    batch_buckets: tuple[int, ...] | None = (1, 8, 64)
    seq_buckets: tuple[int, ...] | None = None
    nfe_buckets: tuple[int, ...] | None = None
    max_batch: int | None = DEFAULT_MAX_BATCH
    max_nfe: int | None = DEFAULT_MAX_NFE
    max_seq_len: int | None = DEFAULT_MAX_SEQ_LEN
    warmup: str = "none"
    warmup_nfes: tuple[int, ...] | None = None
    warmup_seq_lens: tuple[int, ...] | None = None
    compile_cache: bool = False


def make_solver_config(cfg: EngineConfig) -> SolverConfig:
    """The default-solver config an :class:`EngineConfig` implies: a full
    :class:`~repro.core.ERAConfig` for ``era``, the registry default at
    ``cfg.nfe`` for everything else."""
    if cfg.solver == "era":
        return ERAConfig(
            nfe=cfg.nfe, k=cfg.k, lam=cfg.lam, per_sample=cfg.per_sample
        )
    return default_config(cfg.solver, nfe=cfg.nfe)


def build_engine(
    dlm: DiffusionLM,
    schedule: NoiseSchedule,
    cfg: EngineConfig | None = None,
    mesh=None,
    metrics: MetricsRegistry | None = None,
) -> FusedExecutor:
    """Construct the engine every serve mode shares.

    ``mesh`` and ``metrics`` are runtime resources, not engine shape, so
    they ride alongside the config rather than inside it (a mesh is not
    hashable; a registry is per-process state).

    ``cfg.compile_cache`` is applied here (process-global jax config);
    ``cfg.warmup`` is *policy*, not an action — building an engine never
    compiles.  Callers run the warmup themselves once params are in hand:
    ``engine.warmup(params, **warmup_kwargs(cfg))`` (or hand the kwargs to
    :func:`~repro.serving.frontdoor.serve_frontdoor`, which runs it on a
    background thread behind ``/readyz``)."""
    cfg = cfg if cfg is not None else EngineConfig()
    if cfg.warmup not in WARMUP_MODES:
        raise ValueError(
            f"EngineConfig.warmup must be one of {WARMUP_MODES}, "
            f"got {cfg.warmup!r}"
        )
    if cfg.compile_cache:
        configure_persistent_cache()
    return FusedExecutor(
        dlm,
        schedule,
        cfg.solver,
        make_solver_config(cfg),
        batch_buckets=cfg.batch_buckets,
        mesh=mesh,
        seq_buckets=cfg.seq_buckets,
        nfe_buckets=cfg.nfe_buckets,
        metrics=metrics,
        max_batch=cfg.max_batch,
        max_nfe=cfg.max_nfe,
        max_seq_len=cfg.max_seq_len,
    )


def warmup_kwargs(cfg: EngineConfig) -> dict | None:
    """The ``warmup(...)`` keyword set an :class:`EngineConfig` implies —
    ``None`` when ``cfg.warmup == "none"`` (don't warm).  Callers with
    params in hand do::

        kw = warmup_kwargs(cfg)
        if kw is not None:
            engine.warmup(params, **kw)
    """
    if cfg.warmup == "none":
        return None
    # with an nfe-bucket ladder the grid's step counts ARE the ladder
    # (explicit warmup_nfes still fold onto their buckets in the executor);
    # without one, traffic groups by exact nfe, so warm the config's
    default_nfes = None if cfg.nfe_buckets else (cfg.nfe,)
    return {
        "nfes": cfg.warmup_nfes or default_nfes,
        "seq_lens": cfg.warmup_seq_lens,
    }
