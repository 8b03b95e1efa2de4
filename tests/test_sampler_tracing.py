"""Profiler spans, name scopes and counters of the sampler.

* host spans: a chunk run under ``jax.profiler.trace`` yields one
  ``sampler.chunk`` span per chunk with ``sampler.assemble``,
  ``sampler.execute`` and ``sampler.scatter`` nested inside it in that
  order (and ``sampler.compile`` inside the assembly of a chunk that
  compiles); the scheduler's idle waits are ``sampler.wait`` spans;
* counters: ``sampler_queue_wait_seconds`` observes once per request and
  ``sampler_assembly_seconds`` once per chunk;
* device scopes: the compiled bucket program's op metadata carries the
  ``denoiser``, ``era.ers`` and ``era.update`` scopes a device trace splits
  an NFE by.
"""

import re
import time

import jax
import pytest

from conftest import OracleDenoiser, host_spans
from repro.configs import get_config
from repro.core import linear_schedule
from repro.models import build_model
from repro.models.diffusion import DiffusionLM
from repro.serving import (
    AsyncBatchedSampler,
    FusedExecutor,
    SampleRequest,
    SchedulerPolicy,
)

SEQ, NFE = 8, 6


def inside(inner, outer):
    return (inner["line"] == outer["line"] and outer["t"] <= inner["t"]
            and inner["end"] <= outer["end"])


@pytest.fixture(scope="module")
def smoke_engine():
    """The qwen2 smoke preset behind a one-bucket ERA engine (per-sample
    ERS, the fused update kernel), and its weights."""
    dlm = DiffusionLM(build_model(get_config("qwen2-1.5b", smoke=True)))
    params = dlm.init(jax.random.PRNGKey(0))
    return FusedExecutor(dlm, linear_schedule(), batch_buckets=(4,)), params


def test_chunk_spans_nest(smoke_engine, tmp_path):
    eng, params = smoke_engine
    eng._jitted.clear()   # the first chunk compiles in the trace
    sched = AsyncBatchedSampler(eng, params)
    with jax.profiler.trace(str(tmp_path)):
        sched.submit(SampleRequest(batch=1, seq_len=SEQ, nfe=NFE, seed=1))
        sched.submit(SampleRequest(batch=2, seq_len=SEQ, nfe=NFE, seed=2))
        sched.flush()
        sched.submit(SampleRequest(batch=3, seq_len=SEQ, nfe=NFE, seed=3))
        sched.flush()
    spans = host_spans(str(tmp_path))
    chunks = [s for s in spans if s["name"] == "sampler.chunk"]
    assert len(chunks) == 2
    # the chunk's tickets (the scheduler's, from 0) and group key ride as
    # metadata; the name stays bare
    assert chunks[0]["stats"] == {"tickets": "0 1", "key": f"era/{SEQ}/{NFE}"}
    for chunk in chunks:
        kids = [s for s in spans if s is not chunk and inside(s, chunk)]
        order = [s["name"] for s in kids if s["name"] != "sampler.compile"]
        assert order == ["sampler.assemble", "sampler.execute", "sampler.scatter"]
        assemble, execute, scatter = (s for s in kids if s["name"] != "sampler.compile")
        assert assemble["end"] <= execute["t"] and execute["end"] <= scatter["t"]
    # only the first chunk compiles, inside its assembly
    compiles = [s for s in spans if s["name"] == "sampler.compile"]
    assert len(compiles) == 1
    first_assemble = next(s for s in spans if s["name"] == "sampler.assemble")
    assert inside(compiles[0], first_assemble)


def test_bucket_program_metadata_holds_scopes(smoke_engine):
    eng, params = smoke_engine
    eng.warmup(params, seq_lens=(SEQ,), nfes=(NFE,))
    (compiled,) = eng.compile_cache().values()
    op_names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    for scope in ("denoiser", "era.ers", "era.update"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    # the denoiser's matmuls sit under its scope, never under the solver's
    dots = [n for n in op_names if n.endswith("dot_general")]
    assert dots and all("/denoiser/" in n for n in dots)
    assert not any("/era." in n for n in dots)


def test_scheduler_waits_are_spans(analytic, tmp_path):
    eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=(2,)
    )
    sched = AsyncBatchedSampler(eng, None, SchedulerPolicy(max_wait_ms=1.0))
    with jax.profiler.trace(str(tmp_path)):
        with sched:
            time.sleep(0.05)     # nothing queued: the drain thread waits
            sched.submit(SampleRequest(batch=1, seq_len=6, nfe=8)).result()
    spans = host_spans(str(tmp_path))
    waits = [s for s in spans if s["name"] == "sampler.wait"]
    (chunk,) = [s for s in spans if s["name"] == "sampler.chunk"]
    assert waits and all(w["line"] == chunk["line"] for w in waits)
    assert not any(inside(w, chunk) or inside(chunk, w) for w in waits)


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_queue_wait_and_assembly_counters(analytic):
    clock = FakeClock()
    eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=(2,)
    )
    sched = AsyncBatchedSampler(
        eng, None, SchedulerPolicy(max_wait_ms=10.0), clock=clock
    )
    for seed in range(3):
        sched.submit(SampleRequest(batch=1, seq_len=6, nfe=8, seed=seed))
        clock.now += 0.002
    # three rows reach the bucket of 2: the two oldest launch now
    assert sched.drain_once() == 1
    clock.now += 0.02
    # the third launches once its max_wait has passed
    assert sched.drain_once() == 1
    m = eng.metrics
    wait = m.get("sampler_queue_wait_seconds")
    labels = {"solver": "era", "seq": 6, "nfe": 8}
    assert wait.count(**labels) == 3
    assert wait.sum(**labels) == pytest.approx(0.006 + 0.004 + 0.022)
    assembly = m.get("sampler_assembly_seconds")
    assert assembly.count(solver="era") == 2 == m.get("sampler_batches_total").value()
    assert assembly.sum(solver="era") > 0
