"""The benchmark's own arithmetic: traffic, flop counts, file lookup."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import check, flops, loader, traffic  # noqa: E402

SMOKE_QWEN = dict(loader.config("qwen2-1.5b"), **loader.config("qwen2-1.5b")["smoke"])
SMOKE_HYMBA = dict(loader.config("hymba-1.5b"), **loader.config("hymba-1.5b")["smoke"])


# ---- traffic ---------------------------------------------------------------


@pytest.mark.parametrize("name", ["offline_16x1024", "offline_4x1024"])
def test_traffic_is_deterministic_per_seed(name):
    spec = loader.traffic(name)

    def take(seed):
        gen = traffic.generate(spec, seed)
        return [next(gen) for _ in range(100)]

    assert take(2**31 + 7) == take(2**31 + 7)
    assert [r.seed for r in take(1)] != [r.seed for r in take(2)]


MIXED = {"arrivals": {"kind": "closed", "in_flight": 2},
         "rows": {"values": [1, 2, 4], "weights": [0.7, 0.2, 0.1]},
         "seq_len": {"kind": "choice", "values": [256, 512, 1024], "weights": [0.6, 0.3, 0.1]},
         "nfe": 10, "solver": "era"}


def test_seeds_share_their_work():
    n = traffic.CLOSED_BLOCK
    a, b = traffic.generate(MIXED, 5), traffic.generate(MIXED, 6)
    a, b = [next(a) for _ in range(n)], [next(b) for _ in range(n)]
    assert sorted((r.rows, r.seq_len) for r in a) == sorted((r.rows, r.seq_len) for r in b)
    assert [(r.rows, r.seq_len) for r in a] != [(r.rows, r.seq_len) for r in b]
    for r in a:
        assert r.rows in (1, 2, 4) and r.seq_len in (256, 512, 1024)
        assert 0 <= r.seed < traffic.SEED_SPACE


def test_rehearsal_divides_lengths():
    gen = traffic.generate(MIXED, 3, seq_divisor=16)
    for r in [next(gen) for _ in range(traffic.CLOSED_BLOCK)]:
        assert r.seq_len in (16, 32, 64)


def test_open_arrivals_are_refused():
    with pytest.raises(ValueError):
        next(traffic.generate(dict(MIXED, arrivals={"kind": "gamma"}), 1))


# ---- the check's sample ----------------------------------------------------


@pytest.mark.parametrize("rows,k", [(16, 3), (4, 4), (4, 2), (1, 3)])
def test_sample_holds_both_ends_of_each_batch(rows, k):
    reqs = traffic.generate(dict(MIXED, rows={"values": [rows], "weights": [1]}), 9)
    record = {"completed": [{"req": next(reqs), "x0": 0} for _ in range(12)]}
    for seed in range(20):
        picked = check.pick(record, {"requests": 2, "rows_per_request": k}, seed)
        assert len(picked) == 2
        for c, chosen in picked:
            assert record["completed"].index(c) < check.OFFLINE_FIRST
            assert chosen[0] == 0 and chosen[-1] == rows - 1
            assert len(chosen) == min(max(k, 2), rows) == len(set(chosen))


def test_a_missing_limit_is_not_correct():
    ok = {"value": 1e-3, "limit": 5e-3}
    assert check.correct({"x": ok})
    assert not check.correct({"x": ok, "y": {"value": 1e-3, "limit": None}})
    assert not check.correct({"x": ok, "y": {"value": None, "limit": 5e-3}})
    assert not check.correct({"x": {"value": 6e-3, "limit": 5e-3}})


# ---- flop and byte counts --------------------------------------------------


def test_attention_pairs_by_hand():
    assert flops.attention_pairs(8, 0) == 64
    # window 3 over 8 positions, keys |q - k| < 3: q sees 3 at the ends,
    # 4 one in, 5 elsewhere
    assert flops.attention_pairs(8, 3) == 3 + 4 + 5 + 5 + 5 + 5 + 4 + 3
    assert flops.attention_pairs(8, 8) == 64


def test_forward_flops_dense_smoke_by_hand():
    # d=128, ff=256, 4/2 heads of 32, 2 dense layers, time embed 256
    rows, seq = 3, 16
    tokens = rows * seq
    per_layer = 128 * 128 + 2 * 128 * 64 + 128 * 128 + 3 * 128 * 256
    want = (
        2 * tokens * 2 * 128 * 128          # in_proj + eps head
        + 2 * rows * (256 * 128 + 128 * 128)  # time MLP
        + 2 * (2 * tokens * per_layer + 4 * rows * 4 * 32 * seq * seq)
    )
    assert flops.forward_flops(SMOKE_QWEN, rows, seq) == want


def test_forward_flops_hymba_smoke_by_hand():
    rows, seq = 2, 128        # past the smoke window of 64
    tokens = rows * seq
    di, n, dtr = 256, 16, 8
    attn = 128 * 128 + 2 * 128 * 64 + 128 * 128
    mamba = 128 * 2 * di + di * (dtr + 2 * n) + dtr * di + di * 128 + 4 * di
    per_layer = attn + 3 * 128 * 256 + mamba
    full = seq * seq
    swa = sum(min(seq, q + 64) - max(0, q - 64 + 1) for q in range(seq))
    want = (
        2 * tokens * 2 * 128 * 128
        + 2 * rows * (256 * 128 + 128 * 128)
        + 2 * (2 * tokens * per_layer)
        + 4 * rows * 4 * 32 * (full + swa)
    )
    assert flops.forward_flops(SMOKE_HYMBA, rows, seq) == want


def test_kernel_calls_by_hand():
    f, b = flops.flash_attention_call(SMOKE_QWEN, 2, 16, 0)
    assert f == 4 * 2 * 4 * 32 * 256
    assert b == 2 * 2 * 16 * 32 * (2 * 4 + 2 * 2)
    assert flops.flash_calls_per_nfe(SMOKE_HYMBA) == [(0, 1), (64, 1)]


# ---- finding files by name -------------------------------------------------


def test_every_cell_config_traffic_and_metric_is_found():
    bench = loader.benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        w = loader.workload(cell["name"])
        assert w["config"] == cell["config"] and w["traffic"] == cell["traffic"]
        assert w["chips"] == cell["chips"]
        cfg = loader.config(w["config"])
        assert cfg["source"] == configs[w["config"]]["source"]
        assert sorted(cfg["reduced"]) == sorted(configs[w["config"]]["reduced"])
        assert os.path.join(ROOT, configs[w["config"]]["file"]) == os.path.join(
            loader.BENCH, "configs", w["config"] + ".json")
        loader.traffic(w["traffic"])
        assert loader.entry(w["entry"]).run
        ends, layers = loader.cell_metrics(bench, cell["name"])
        names = {m["name"] for m in ends}
        assert "setup_s" in names and len(names) >= 2 and layers
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert loader.metric(m["name"]).read


def test_unknown_device_kind_is_an_error():
    assert loader.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        loader.peaks("TPU v9 imaginary")


def test_a_cell_without_a_limit_is_refused(tmp_path, monkeypatch):
    (tmp_path / "workloads").mkdir()
    w = loader.workload("qwen2-1.5b.offline")
    for drop in ("limit", "rehearse_limit"):
        check_spec = {k: v for k, v in w["check"].items() if k != drop}
        (tmp_path / "workloads" / "x.json").write_text(json.dumps(dict(w, check=check_spec)))
        monkeypatch.setattr(loader, "BENCH", str(tmp_path))
        with pytest.raises(SystemExit):
            loader.workload("x")


def test_unknown_names_are_errors():
    for fn in (loader.workload, loader.config, loader.traffic, loader.entry, loader.metric):
        with pytest.raises(SystemExit):
            fn("no-such-name")


def test_benchmark_file_keeps_the_contract_shapes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in ends
        for cell in m["workloads"]:
            reported = [e["name"] for e in bench["end_to_end"]
                        if cell in e.get("workloads", [cell])]
            assert m["moves"] in reported
