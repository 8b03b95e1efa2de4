"""Sharding-rule correctness (pure pspec logic — no devices needed), the
sampling-engine carry specs, mesh-sharded drain placement (8-virtual-device
fixture), and the dry-run plumbing (subprocess, marked slow)."""

import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from conftest import OracleDenoiser
from repro.configs import INPUT_SHAPES, arch_names, get_config
from repro.launch.specs import build_program, train_microbatches
from repro.models import build_model
from repro.parallel.sharding import (
    ParamReplicator,
    ShardingRules,
    round_to_dp,
    sampler_pspecs,
    sampler_shardings,
)


class FakeMesh:
    """Just enough Mesh surface for pspec derivation."""

    def __init__(self, shape: dict):
        self._shape = shape

    @property
    def axis_names(self):
        return tuple(self._shape)

    @property
    def shape(self):
        return self._shape


def rules_for(name, multi=False, fsdp=False):
    shape = (
        {"pod": 2, "data": 16, "model": 16} if multi else {"data": 16, "model": 16}
    )
    return ShardingRules(get_config(name), FakeMesh(shape), fsdp=fsdp)


def _leaves_with_paths(tree):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path), leaf


@pytest.mark.parametrize("name", arch_names())
@pytest.mark.parametrize("fsdp", [False, True])
def test_param_specs_divisible(name, fsdp):
    """Every sharded dim must divide by its mesh axes (else jit rejects)."""
    rules = rules_for(name, fsdp=fsdp)
    model = build_model(get_config(name))
    aparams = model.init_abstract()
    specs = rules.param_pspec(aparams)
    mesh_shape = {"data": 16, "model": 16}
    for (path, leaf), (_, spec) in zip(
        _leaves_with_paths(aparams), _leaves_with_paths(specs)
    ):
        for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if part is None:
                continue
            parts = (part,) if isinstance(part, str) else part
            total = 1
            for ax in parts:
                total *= mesh_shape[ax]
            assert dim % total == 0, (name, path, leaf.shape, spec)


def test_tensor_parallel_actually_used():
    rules = rules_for("llama3.2-1b")
    model = build_model(get_config("llama3.2-1b"))
    specs = rules.param_pspec(model.init_abstract())
    flat = dict(_leaves_with_paths(specs))
    assert flat["segs/0_dense/mlp/wi/w"] == P(None, None, "model")
    assert flat["segs/0_dense/mlp/wo/w"] == P(None, "model", None)
    assert flat["embed"] == P("model", None)


def test_expert_parallel_for_deepseek():
    rules = rules_for("deepseek-v2-lite-16b")
    model = build_model(get_config("deepseek-v2-lite-16b"))
    specs = rules.param_pspec(model.init_abstract())
    flat = dict(_leaves_with_paths(specs))
    # 64 experts / 16 shards -> expert-parallel
    assert flat["segs/1_mla_moe/moe/experts/wi"] == P(None, "model", None, None)


def test_mixtral_experts_tensor_parallel():
    rules = rules_for("mixtral-8x7b")
    model = build_model(get_config("mixtral-8x7b"))
    specs = rules.param_pspec(model.init_abstract())
    flat = dict(_leaves_with_paths(specs))
    # 8 experts don't divide 16 -> ff-dim tensor parallel
    assert flat["segs/0_moe/moe/experts/wi"] == P(None, None, None, "model")
    assert flat["segs/0_moe/moe/experts/wo"] == P(None, None, "model", None)


def test_fsdp_excludes_embeddings():
    rules = rules_for("deepseek-67b", fsdp=True)
    model = build_model(get_config("deepseek-67b"))
    specs = rules.param_pspec(model.init_abstract())
    flat = dict(_leaves_with_paths(specs))
    assert "data" not in str(flat["embed"])
    assert "data" in str(flat["segs/0_dense/mlp/wi/w"])


# ---------------------------------------------------------------------------
# sampling-engine carry specs (pure pspec logic)
# ---------------------------------------------------------------------------


def test_sampler_pspecs_batch_sharded_carry():
    """Latents/eps buffer shard the batch dim over the data axes; the time
    grid replicates; per-sample delta_eps follows the batch."""
    specs = sampler_pspecs(FakeMesh({"data": 8}), batch=16, per_sample=True)
    assert specs.x == P(("data",), None, None)
    assert specs.eps_buf == P(None, ("data",), None, None)
    assert specs.t_buf == P()
    assert specs.delta_eps == P(("data",))


def test_sampler_pspecs_multi_pod_and_shared_delta():
    mesh = FakeMesh({"pod": 2, "data": 8, "model": 2})
    specs = sampler_pspecs(mesh, batch=16, per_sample=False)
    assert specs.x == P(("pod", "data"), None, None)
    assert specs.delta_eps == P()  # shared scalar delta replicates


def test_sampler_pspecs_non_divisible_batch_replicates():
    """An exact-size (unpadded) batch that doesn't divide dp must degrade to
    replicated specs, never a ragged-shard error."""
    specs = sampler_pspecs(FakeMesh({"data": 8}), batch=3, per_sample=True)
    assert specs.x == P(None, None, None)
    assert specs.eps_buf == P(None, None, None, None)
    assert specs.delta_eps == P(None)


def test_round_to_dp():
    mesh = FakeMesh({"data": 8})
    assert round_to_dp(1, mesh) == 8
    assert round_to_dp(8, mesh) == 8
    assert round_to_dp(9, mesh) == 16
    assert round_to_dp(5, None) == 5


def test_solver_program_carry_pspecs():
    """PR-4: carry pspecs derive from the program's declared state — ERA's
    per-sample ERS shards delta_eps with its rows, shared-delta ERA and
    every baseline replicate it; the rest of the carry is the shared
    batch-over-data-axes layout."""
    from repro.core import ERAConfig, default_config, get_program
    from repro.parallel.sharding import solver_carry_pspecs

    mesh = FakeMesh({"data": 8})
    era = get_program("era")
    specs = solver_carry_pspecs(mesh, era, ERAConfig(per_sample=True), batch=16)
    assert specs.delta_eps == P(("data",))
    assert specs.eps_buf == P(None, ("data",), None, None)
    specs = solver_carry_pspecs(mesh, era, ERAConfig(), batch=16)
    assert specs.delta_eps == P()  # shared scalar delta replicates
    for name in ("ddim", "explicit_adams", "dpm_solver_pp2m"):
        program = get_program(name)
        cfg = default_config(name)
        assert not program.per_sample_state(cfg)
        specs = program.carry_pspecs(cfg, mesh, batch=16)
        assert specs.x == P(("data",), None, None)
        assert specs.t_buf == P()


def test_param_replicator_invalidates_on_leaf_change():
    """The placement cache keys on leaf identity, so mutating the params
    container in place (finetune-and-sample loop) gets fresh weights instead
    of the first call's stale copy.  Works on any device count (a 1-device
    mesh replicates trivially)."""
    import jax.numpy as jnp

    from repro.launch.mesh import make_sampler_mesh

    rep = ParamReplicator(make_sampler_mesh(1))
    params = {"w": jnp.ones((4,)), "b": jnp.zeros((2,))}
    first = rep(params)
    assert rep(params) is first                   # same leaves -> cached
    params["w"] = jnp.full((4,), 2.0)             # in-place container mutation
    second = rep(params)
    assert second is not first
    assert float(second["w"][0]) == 2.0


# ---------------------------------------------------------------------------
# mesh-sharded drain placement (8-virtual-device fixture; the CI sharded job
# runs these in-process, single-device runs cover parity via the subprocess
# test in test_batched_sampler.py)
# ---------------------------------------------------------------------------


def test_sampler_shardings_on_real_mesh(mesh8):
    sh = sampler_shardings(mesh8, batch=8, per_sample=True)
    assert sh.x.spec == P(("data",), None, None)
    assert len(sh.x.mesh.devices.ravel()) == 8


def test_mesh_drain_places_rows_across_devices(mesh8, analytic):
    import numpy as np

    from conftest import run_flush
    from repro.serving import FusedExecutor, SampleRequest

    eng = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, mesh=mesh8
    )
    assert eng.dp == 8
    req = SampleRequest(batch=8, seq_len=6, nfe=6, seed=0)
    (res,) = run_flush(eng, [req])
    assert res.padded_batch == 8
    # one row per device: the batch really ran data-parallel
    assert len(res.x0.sharding.device_set) == 8
    solo = FusedExecutor(
        OracleDenoiser(analytic), analytic.schedule, batch_buckets=None
    )
    np.testing.assert_allclose(
        np.asarray(res.x0),
        np.asarray(run_flush(solo, [req])[0].x0),
        atol=1e-5,
    )


def test_microbatch_heuristic():
    cfg = get_config("deepseek-67b")
    assert train_microbatches(cfg, INPUT_SHAPES["train_4k"], dp=16) == 16
    small = get_config("whisper-base")
    assert train_microbatches(small, INPUT_SHAPES["train_4k"], dp=16) == 1


@pytest.mark.parametrize("name", arch_names())
def test_programs_build_for_all_shapes(name):
    """Abstract programs assemble for all 4 input shapes (no allocation)."""
    model = build_model(get_config(name))
    for shape in INPUT_SHAPES.values():
        prog = build_program(model, shape)
        assert prog.args, (name, shape.name)


@pytest.mark.slow
def test_dryrun_subprocess_single_combo(tmp_path):
    """Real 512-placeholder-device lower+compile of one combo."""
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "llama3.2-1b", "--shape", "decode_32k",
            "--mesh", "multi", "--out", str(tmp_path),
        ],
        capture_output=True, text=True, timeout=600, cwd="/root/repo", env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(
        (tmp_path / "llama3.2-1b__decode_32k__multi.json").read_text()
    )
    assert rec["ok"] and rec["num_devices"] == 512
    assert rec["hlo"]["flops"] > 0
