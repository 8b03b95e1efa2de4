"""Cold-start contract: AOT grid warmup compiles without sampling, the
persistent compilation cache survives process boots, and ``/readyz``
gates traffic on warmup.

The wall these tests form around :meth:`FusedExecutor.warmup`:

* warmup populates the full program grid with **zero** sampling — no
  ``run_chunk`` calls, no batches run — and serving after it is pure
  memory hits with output bit-identical to a cold engine's;
* a second process boot against the same ``JAX_COMPILATION_CACHE_DIR``
  loads its programs from disk instead of compiling them;
* the front door answers ``/readyz`` 503 (with progress) until warmup
  finishes, 200 after, and stays 503 with the error when warmup dies —
  while ``/healthz`` stays pure liveness throughout.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from conftest import OracleDenoiser, run_flush
from repro.serving import (
    EngineConfig,
    FrontDoorClient,
    FusedExecutor,
    SampleRequest,
    SchedulerPolicy,
    build_engine,
    serve_frontdoor,
    warmup_kwargs,
)

D_MODEL = OracleDenoiser.D_MODEL
BATCHES = (1, 2)
SEQS = (4, 8)


@pytest.fixture()
def engine(analytic):
    return FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        batch_buckets=BATCHES,
        seq_buckets=SEQS,
    )


def grid_requests(nfe=10):
    seed = iter(range(100))
    return [
        SampleRequest(batch=b, seq_len=s, nfe=nfe, seed=next(seed))
        for s in SEQS
        for b in BATCHES
    ]


# ---------------------------------------------------------------------------
# warmup compiles the grid without sampling
# ---------------------------------------------------------------------------


def test_warmup_compiles_grid_without_sampling(engine, monkeypatch):
    ex = engine
    chunks = []
    real_run_chunk = ex.run_chunk
    monkeypatch.setattr(
        ex, "run_chunk", lambda *a, **kw: chunks.append(a) or real_run_chunk(*a, **kw)
    )

    report = engine.warmup(None)

    # no sampling happened: no chunk ran, no batch was counted
    assert chunks == []
    assert ex._m_batches.value() == 0
    # the full (batch x seq) grid at the config nfe, all fresh compiles
    assert report["programs"] == len(BATCHES) * len(SEQS)
    assert report["fresh"] == report["programs"]
    assert report["disk"] == 0 and report["memory"] == 0
    assert len(engine.compile_cache()) == report["programs"]
    assert {g["nfe"] for g in report["grid"]} == {ex.solver_config.nfe}
    # instruments agree
    assert ex._m_warmup_total.value() == report["programs"]
    assert ex._m_warmup_done.value() == report["programs"]
    assert ex._m_warmup_inflight.value() == 0
    assert ex._m_warmup_wall.value() > 0
    assert engine.warmup_status()["state"] == "done"


def test_warmed_engine_serves_grid_with_zero_fresh_compiles(engine, analytic):
    engine.warmup(None)
    fresh_after_warmup = engine.compile_stats()["fresh"]

    cold = FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        batch_buckets=BATCHES,
        seq_buckets=SEQS,
    )
    for r in grid_requests(nfe=engine.solver_config.nfe):
        (warm,) = run_flush(engine, [r])
        (cold_res,) = run_flush(cold, [r])
        # warmed programs == cold-compiled programs, bit for bit
        np.testing.assert_array_equal(
            np.asarray(warm.x0), np.asarray(cold_res.x0)
        )
    # every serving-path acquisition was a memory hit
    assert engine.compile_stats()["fresh"] == fresh_after_warmup


def test_warmup_progress_callback_counts_grid(engine):
    calls = []
    engine.warmup(None, progress=lambda done, total: calls.append((done, total)))
    n = len(BATCHES) * len(SEQS)
    assert calls == [(i, n) for i in range(1, n + 1)]


def test_second_warmup_is_memory_hits(engine):
    first = engine.warmup(None)
    again = engine.warmup(None)
    assert again["memory"] == first["programs"]
    assert again["fresh"] == 0


def test_warmup_extra_nfes_extend_grid(engine):
    report = engine.warmup(None, nfes=(6, 10))
    assert report["programs"] == 2 * len(BATCHES) * len(SEQS)
    assert {g["nfe"] for g in report["grid"]} == {6, 10}


def test_warmup_rejects_unserveable_grid(engine):
    # ERA needs nfe >= k; a grid no request could use must fail the boot
    with pytest.raises(ValueError):
        engine.warmup(None, nfes=(2,))
    assert len(engine.compile_cache()) == 0


def test_warmup_without_ladder_needs_seq_lens(analytic):
    eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=BATCHES
    )
    with pytest.raises(ValueError, match="seq_lens"):
        eng.warmup(None)
    report = eng.warmup(None, seq_lens=(6,))
    assert report["programs"] == len(BATCHES)
    run_flush(eng, [SampleRequest(batch=1, seq_len=6, nfe=10, seed=0)])
    assert eng.compile_stats()["memory"] == 1


def test_warmup_kwargs_follow_engine_config():
    assert warmup_kwargs(EngineConfig(warmup="none")) is None
    kw = warmup_kwargs(
        EngineConfig(warmup="grid", nfe=8, warmup_seq_lens=(16,))
    )
    assert kw == {"nfes": (8,), "seq_lens": (16,)}
    # with an NFE ladder the ladder drives the warmup grid, not the
    # config's single nfe — warmup defaults to |nfe_buckets| step counts
    kw = warmup_kwargs(EngineConfig(warmup="grid", nfe=8, nfe_buckets=(8, 16)))
    assert kw == {"nfes": None, "seq_lens": None}
    with pytest.raises(ValueError, match="warmup"):
        build_engine(None, None, EngineConfig(warmup="bogus"))


# ---------------------------------------------------------------------------
# NFE-bucketed warmup: the grid is |nfe_buckets| wide, not |nfes|
# ---------------------------------------------------------------------------

NFE_BUCKETS = (8, 16)


def _nfe_bucketed_engine(analytic):
    return FusedExecutor(
        OracleDenoiser(analytic),
        analytic.schedule,
        batch_buckets=BATCHES,
        seq_buckets=SEQS,
        nfe_buckets=NFE_BUCKETS,
    )


def test_warmup_grid_bounded_by_nfe_buckets(analytic):
    eng = _nfe_bucketed_engine(analytic)
    report = eng.warmup(None)
    assert report["programs"] == len(BATCHES) * len(SEQS) * len(NFE_BUCKETS)
    assert {g["nfe"] for g in report["grid"]} == set(NFE_BUCKETS)

    # explicit nfes fold onto their buckets: eight distinct traffic NFEs
    # warm |nfe_buckets| step counts, not eight
    eng2 = _nfe_bucketed_engine(analytic)
    report2 = eng2.warmup(None, nfes=(5, 6, 7, 8, 9, 12, 14, 16))
    assert report2["programs"] == (
        len(BATCHES) * len(SEQS) * len(NFE_BUCKETS)
    )
    assert {g["nfe"] for g in report2["grid"]} == set(NFE_BUCKETS)


def test_warmed_engine_serves_mixed_nfes_memory_hit_only(analytic):
    eng = _nfe_bucketed_engine(analytic)
    eng.warmup(None)
    fresh_after_warmup = eng.compile_stats()["fresh"]
    served = []
    for i, (nfe, seq) in enumerate(
        [(5, 3), (8, 4), (10, 7), (16, 8), (6, 5), (13, 2)]
    ):
        (res,) = run_flush(
            eng, [SampleRequest(batch=1, seq_len=seq, nfe=nfe, seed=i)]
        )
        served.append((res, nfe))
    for res, nfe in served:
        assert res.padded_nfe in NFE_BUCKETS and res.padded_nfe >= nfe
    # post-warmup mixed-NFE serving is pure memory hits
    assert eng.compile_stats()["fresh"] == fresh_after_warmup


# ---------------------------------------------------------------------------
# persistent compilation cache across process boots
# ---------------------------------------------------------------------------


def _boot_subprocess(cache_dir, timeout=600):
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(tests_dir)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(tests_dir, "_coldstart_boot_main.py")],
        capture_output=True, text=True, timeout=timeout, cwd=root, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_persistent_cache_round_trip_across_boots(tmp_path):
    cache_dir = tmp_path / "compile-cache"
    first = _boot_subprocess(cache_dir)
    assert first["warmup"]["fresh"] == first["warmup"]["programs"] > 0
    assert first["warmup"]["disk"] == 0
    assert len(os.listdir(cache_dir)) > 0  # programs hit the disk

    second = _boot_subprocess(cache_dir)
    # the redeploy boot loads instead of compiling ...
    assert second["warmup"]["fresh"] < first["warmup"]["fresh"]
    assert second["warmup"]["disk"] > 0
    assert second["warmup"]["disk"] + second["warmup"]["fresh"] == (
        second["warmup"]["programs"]
    )
    # ... and serves the same numbers
    assert second["x0_sum"] == first["x0_sum"]


def test_cache_dir_is_env_else_fixed_checkout_path(monkeypatch, tmp_path):
    """One rule places the persistent cache: the environment when it says,
    else one fixed directory at the checkout root (never a per-run name)."""
    from repro.serving import cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache_dir() == os.path.join(root, ".jax_cache")
    assert cache_dir() == cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache_dir() == str(tmp_path)


def test_cache_configured_after_first_compile_still_takes_effect(
    analytic, tmp_path, monkeypatch
):
    """Regression: jax latches its cache handle at the first compile of
    the process; configure_persistent_cache must un-latch it or a cache
    dir configured after any compile is silently ignored."""
    from repro.serving import configure_persistent_cache

    def boot():
        eng = FusedExecutor(
            OracleDenoiser(analytic), analytic.schedule,
            batch_buckets=(1,), seq_buckets=(4,),
        )
        return eng.warmup(None)

    boot()  # a compile before any cache dir exists (latches jax's handle)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    assert configure_persistent_cache() == str(tmp_path / "cache")
    try:
        assert boot()["fresh"] == 1  # writes
        assert boot()["disk"] == 1  # reads
    finally:
        # drop the dir AND re-latch, or every later compile in this pytest
        # process would keep reading/writing the tmp cache
        from jax._src import compilation_cache as _cc

        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()


# ---------------------------------------------------------------------------
# /readyz gates on warmup; /healthz stays liveness
# ---------------------------------------------------------------------------


def _ready_door(analytic, warmup):
    eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule,
        batch_buckets=BATCHES, seq_buckets=SEQS,
    )
    return serve_frontdoor(
        eng, None, SchedulerPolicy(max_wait_ms=5.0), warmup=warmup
    )


def test_readyz_gates_on_warmup(analytic):
    release = threading.Event()
    started = threading.Event()

    def slow_warmup():
        started.set()
        assert release.wait(timeout=60)
        return {"programs": 0}

    door = _ready_door(analytic, slow_warmup)
    try:
        client = FrontDoorClient(door.url, timeout=60)
        assert started.wait(timeout=60)
        # warmup held open: not ready, but alive
        not_ready = client.readyz()
        assert not_ready["ready"] is False
        assert "warmup" in not_ready
        assert client.healthz()["ok"] is True
        assert door.ready is False

        release.set()
        deadline = threading.Event()
        for _ in range(600):
            if client.readyz()["ready"]:
                break
            deadline.wait(0.05)
        ready = client.readyz()
        assert ready["ready"] is True
        assert door.ready is True
    finally:
        release.set()
        door.stop()


def test_readyz_stays_503_when_warmup_fails(analytic):
    def broken_warmup():
        raise RuntimeError("no such solver")

    door = _ready_door(analytic, broken_warmup)
    try:
        client = FrontDoorClient(door.url, timeout=60)
        door._warmup_thread.join(timeout=60)
        payload = client.readyz()
        assert payload["ready"] is False
        assert "no such solver" in payload["error"]
        assert client.healthz()["ok"] is True  # liveness unaffected
    finally:
        door.stop()


def test_readyz_immediate_without_warmup(analytic):
    door = _ready_door(analytic, None)
    try:
        assert FrontDoorClient(door.url, timeout=60).readyz()["ready"] is True
    finally:
        door.stop()


def test_readyz_with_real_grid_warmup(analytic):
    cfg = EngineConfig(nfe=6, k=3, batch_buckets=BATCHES, seq_buckets=SEQS,
                       warmup="grid")
    eng = build_engine(OracleDenoiser(analytic), analytic.schedule, cfg)
    door = serve_frontdoor(
        eng, None, SchedulerPolicy(max_wait_ms=5.0),
        warmup=warmup_kwargs(cfg),
    )
    try:
        client = FrontDoorClient(door.url, timeout=600)
        waiter = threading.Event()
        for _ in range(1200):
            if client.readyz()["ready"]:
                break
            waiter.wait(0.1)
        payload = client.readyz()
        assert payload["ready"] is True
        assert payload["warmup"]["state"] == "done"
        assert payload["warmup"]["total"] == len(BATCHES) * len(SEQS)
        # first request of a warmed shape is a memory hit, not a compile
        fresh_before = eng.compile_stats()["fresh"]
        res = client.sample(SampleRequest(batch=2, seq_len=8, nfe=6, seed=3))
        assert res.x0.shape == (2, 8, D_MODEL)
        assert eng.compile_stats()["fresh"] == fresh_before
    finally:
        door.stop()
