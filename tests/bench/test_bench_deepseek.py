"""The ``deepseek-v2-lite.offline`` cell: its whole run rehearsed on the CPU
at the smoke sizes, the float8 control failing its check, the work counts
of its two layer kinds (``bench/layers/mla_moe.py``, ``mla_dense.py``) by
hand, and the program held to every MLA, MoE and YaRN width of its file."""

import json
import os
import shutil
import sys

import pytest

import benchproc

ROOT = benchproc.ROOT
sys.path.insert(0, ROOT)

from bench import flops, loader, weights  # noqa: E402

CELL = "deepseek-v2-lite.offline"
CONFIG = "deepseek-v2-lite"


@pytest.fixture(autouse=True)
def _fresh_layer_modules():
    """Leave no layer module cached for the other tests of the process."""
    loader.layer.cache_clear()
    yield
    loader.layer.cache_clear()


def _line(args, tmp_path):
    line = benchproc.last_line(benchproc.run(args, tmp_path))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and "TPU" not in json.dumps(line)
    return line


def test_rehearsal_last_line(tmp_path):
    line = _line(["bench/run.py", "--workload", CELL, "--seed", str(2**31 + 11),
                  "--seconds", "3", "--trace", "0", "--rehearse"], tmp_path)
    assert set(line["metrics"]) == {"samples_per_s", "setup_s"}
    assert line["compared"]["x0_relerr_max"]["value"] <= 1e-3


def test_traced_rehearsal_last_line(tmp_path):
    line = _line(["bench/run.py", "--workload", CELL, "--seed", "5",
                  "--seconds", "1", "--trace", "1", "--rehearse"], tmp_path)
    # the roofline and MFU readers need a chip's peaks: none on the CPU
    assert set(line["metrics"]) == {"nfe_ms.offline", "device_idle.offline"}
    assert line["device"]["busy_s"] > 0


def test_control_fails_where_the_program_passes(tmp_path):
    """As ``test_bench_control`` for the other cells: seeds on which no row
    of the smoke model flips an error-robust selection or a top-2 choice
    between bfloat16 and float32."""
    proc = benchproc.run(
        [os.path.join("bench", "calibrate.py"), "--workload", CELL,
         "--seeds", "21,23", "--seconds", "3", "--control-seeds", "2",
         "--rehearse"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    *seeds, summary = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert summary["control_seeds"] == 2 and summary["as_expected"] is True
    for s in seeds:
        assert s["program"] <= s["limit"] < s["control"], s


# ---- work counts by hand -----------------------------------------------------


def test_work_counts_by_hand():
    """At the cell's 16 x 1024: d=2048, 16 heads, q/k 192 = 128 + 64, v 128,
    latent 512, dense MLP 10944, experts 1408 (8 held of 64, top-6, 2
    shared)."""
    cfg = loader.config(CONFIG)
    rows, seq = 16, 1024
    n, pairs = rows * seq, rows * seq * seq
    moe, dense = loader.layer("mla_moe"), loader.layer("mla_dense")
    proj = 2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * (128 + 128) + 16 * 128 * 2048
    mla = 2.0 * n * proj + 2.0 * 16 * pairs * (192 + 128)
    assert moe.mla_flops(cfg, rows, seq) == mla
    assignments = n * 6 * 8 / 64                     # 1536 for each held expert
    assert assignments / 8 == 1536
    experts = 2.0 * assignments * 3 * 2048 * 1408
    assert moe.expert_work(cfg, rows, seq) == (
        experts, 2.0 * (8 * 3 * 2048 * 1408 + 2 * assignments * 2048))
    routed = 2.0 * n * 2048 * 64 + 2.0 * n * 3 * 2048 * 2 * 1408 + experts
    assert moe.matmul_flops(cfg, rows, seq) == mla + routed
    assert dense.matmul_flops(cfg, rows, seq) == mla + 2.0 * n * 3 * 2048 * 10944
    call = (2.0 * 16 * pairs * (192 + 128), 2.0 * n * 16 * (2 * 192 + 2 * 128))
    assert flops.flash_work(cfg, rows, seq) == [(*call, 1), (*call, 13)]
    common = 2.0 * n * 2 * 2048 * 2048 + 2.0 * rows * (256 * 2048 + 2048 * 2048)
    total = common + dense.matmul_flops(cfg, rows, seq) + 13 * moe.matmul_flops(cfg, rows, seq)
    assert flops.forward_flops(cfg, rows, seq) == total
    assert 21.3e12 < total < 21.5e12


# ---- the program is held to the file's widths --------------------------------

KEYS = ["kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "moe_intermediate_size", "n_routed_experts", "n_routed_experts_published",
        "num_experts_per_tok", "n_shared_experts"]


@pytest.mark.parametrize("rehearse", [False, True], ids=["full", "smoke"])
def test_the_file_holds_the_program(rehearse):
    weights.program_config(loader.config(CONFIG), rehearse)


@pytest.mark.parametrize("key", KEYS)
def test_a_lowered_width_is_refused(key, tmp_path):
    path = tmp_path / "c.json"
    shutil.copy(os.path.join(loader.BENCH, "configs", CONFIG + ".json"), path)
    cfg = json.loads(path.read_text())
    cfg[key] = cfg[key] // 2
    with pytest.raises(SystemExit, match=key):
        weights.program_config(cfg, False)


@pytest.mark.parametrize("change", [("factor", 20), ("mscale_all_dim", 0.0),
                                    ("original_max_position_embeddings", 2048)])
def test_a_changed_yarn_setting_is_refused(change):
    cfg = loader.config(CONFIG)
    cfg = dict(cfg, rope_scaling=dict(cfg["rope_scaling"], **dict([change])))
    with pytest.raises(SystemExit, match="rope_scaling"):
        weights.program_config(cfg, False)


def test_the_held_experts_are_the_files():
    """Another rank's share (experts 8-15) is not this file's."""
    cfg = dict(loader.config(CONFIG), first_routed_expert_held=8)
    with pytest.raises(SystemExit, match="first_routed_expert_held"):
        weights.program_config(cfg, False)


def test_expert_leaves_are_drawn_by_their_kind():
    """``moe/experts/{wi,wg,wo}`` have no rule in ``bench/weights.py``: the
    kind draws them, normal(0, 1/fan_in) over their input width."""
    import numpy as np
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    pcfg = weights.program_config(loader.config(CONFIG), True)
    params = weights.make_weights(DiffusionLM(build_model(pcfg)).init_abstract(), 7, 0.01)
    experts = params["backbone"]["segs"]["1_mla_moe"]["moe"]["experts"]
    assert experts["wi"].shape == (1, 2, 128, 128)       # 2 of the smoke's 4 held
    for name, leaf in experts.items():
        std = float(np.std(np.asarray(leaf)))
        assert abs(std * np.sqrt(leaf.shape[-2]) - 1.0) < 0.05, (name, std)
    with pytest.raises(ValueError, match="no rule"):
        loader.layer("mla_moe").init_leaf(("backbone", "segs", "1_mla_moe", "moe", "x"),
                                          (2, 2), None, 0.01)


def test_router_is_drawn_device_balanced():
    """The router's columns of each rank's experts sum to zero, so every
    input's logits sum to zero over each rank; each column keeps variance
    1/d; with fewer experts than ranks the draw is the plain one."""
    import jax
    import numpy as np

    mod = loader.layer("mla_moe")
    key = jax.random.PRNGKey(3)
    w = np.asarray(mod.router((2, 256, 64), key), np.float64)
    ranks = w.reshape(2, 256, mod.EP_RANKS, 8)
    assert np.abs(ranks.sum(-1)).max() < 1e-5
    h = np.random.default_rng(0).standard_normal((5, 256)) + 3.0     # a common part
    logits = np.einsum("nd,ldr->lnr", h, w).reshape(2, 5, mod.EP_RANKS, 8)
    assert np.abs(logits.sum(-1)).max() < 1e-4
    assert abs(np.std(w) * np.sqrt(256) - 1.0) < 0.02
    plain = jax.random.normal(key, (2, 256, 4)) / np.sqrt(256)
    np.testing.assert_array_equal(mod.router((2, 256, 4), key), plain)
    assert mod.init_leaf(("backbone", "segs", "1_mla_moe", "moe", "router"),
                         (2, 256, 64), key, 0.01).shape == (2, 256, 64)
