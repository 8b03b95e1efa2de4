"""Layer kinds as modules (``bench/layers``): the counts, weights and
reference answers they give are the ones the benchmark gave before they
were moved there, and a new kind joins by adding files alone.

The literals below were read from the benchmark before its per-kind code
moved into ``bench/layers`` (the same functions, on the CPU), and each is
compared exactly.  The weights and the reference's eps are taken at the
smoke sizes from the program's own parameter shapes, as a rehearsal draws
them; flop and byte counts also at each cell's own shape."""

import hashlib
import json
import os
import shutil
import sys
import textwrap

import pytest

import benchproc

ROOT = benchproc.ROOT
sys.path.insert(0, ROOT)

from bench import flops, harness, loader, reference, weights  # noqa: E402

CONFIGS = ["qwen2-1.5b", "hymba-1.5b"]

HYMBA_FULL_FLASH = [(26843545600.0, 31457280.0, n) for n in (1, 14, 1, 15, 1)]

#: (config, sizes, rows, seq) -> (forward flops, [(flash flops, bytes, layers)])
COUNTS = {
    ("qwen2-1.5b", "full", 16, 1024): (45973418016768.0, [(103079215104.0, 117440512.0, 28)]),
    ("qwen2-1.5b", "smoke", 16, 64): (739770368.0, [(33554432.0, 786432.0, 2)]),
    ("qwen2-1.5b", "smoke", 3, 16): (32538624.0, [(393216.0, 36864.0, 2)]),
    ("hymba-1.5b", "full", 4, 1024): (13661710745600.0, HYMBA_FULL_FLASH),
    ("hymba-1.5b", "smoke", 4, 64): (
        299237376.0, [(8388608.0, 196608.0, 1), (8388608.0, 196608.0, 1)]),
    ("hymba-1.5b", "smoke", 2, 128): (
        311558144.0, [(16777216.0, 196608.0, 1), (12517376.0, 196608.0, 1)]),
}

#: the readers over a fixed record at the cell's shape (``_record``)
READERS = {
    "qwen2-1.5b": {"step_mfu.offline": 47.56537154568774,
                   "flash_attention_roofline.offline": 7.470160819978454},
    "hymba-1.5b": {"step_mfu.offline": 14.134784307905203,
                   "flash_attention_roofline.offline": 1.9453543802027224},
}
CELL_SHAPE = {"qwen2-1.5b": (16, 1024), "hymba-1.5b": (4, 1024)}

WEIGHTS_SHA256 = {
    "qwen2-1.5b": "6bb9de61673d7913aa6ff01426d4290e0ccc2e5bb52b874e2f511619b1bc0f43",
    "hymba-1.5b": "52dac8ac4eb37ff3fcfb0c01a3ddf40f218dec64a27f6cdadff766c56b23a65a",
}
WEIGHTS_SEED = 2**31 + 5

#: eps over request_noise(2**31 + 9, 2, 64, d) at t = 0.37: sha256, sum
EPS = {
    ("qwen2-1.5b", "f32"): ("e15f274954d807b46a40e1659913014a97901da4697b6fa810bee283235b78dc",
                            "0x1.e0e2b4eb94000p+5"),
    ("qwen2-1.5b", "fp8"): ("72c194d660c20541167134fcaa000923a648d30ed86e9df6bb7d94912724052b",
                            "0x1.e58286dd9c000p+5"),
    ("hymba-1.5b", "f32"): ("3a58d7c065a1ce1ecb4e6c78a0eb769ca844a5baa31ca7c768d49a3fc1c040a9",
                            "0x1.6d2c9511a4000p+6"),
    ("hymba-1.5b", "fp8"): ("e70d78a7cc842911838c9af7ff948d19a6f2059c9b03704e3bbf6c4faf2b3956",
                            "0x1.6dc62cf19e000p+6"),
}


def _cfg(name, sizes):
    f = loader.config(name)
    return dict(f, **f["smoke"]) if sizes == "smoke" else f


# ---- parity with the counts, weights and answers before the move -----------


@pytest.mark.parametrize("key", sorted(COUNTS), ids=lambda k: "-".join(map(str, k)))
def test_counts_match_the_parent(key):
    name, sizes, rows, seq = key
    cfg = _cfg(name, sizes)
    want_flops, want_flash = COUNTS[key]
    assert flops.forward_flops(cfg, rows, seq) == want_flops
    assert flops.flash_work(cfg, rows, seq) == want_flash
    assert [(*flops.flash_attention_call(cfg, rows, seq, w), n)
            for w, n in flops.flash_calls_per_nfe(cfg)] == want_flash


class _Req:
    def __init__(self, rows, seq_len, nfe):
        self.rows, self.seq_len, self.nfe = rows, seq_len, nfe


def _record(name):
    rows, seq = CELL_SHAPE[name]
    completed = [
        {"req": _Req(rows, seq, 10), "x0": 1, "batch_wall_s": 4.5 + i // 2,
         "padded_batch": rows, "padded_seq_len": seq, "padded_nfe": 10}
        for i in range(8)
    ]
    return {"entry": "offline", "completed": completed, "window_s": 39.25, "chips": 1,
            "config": loader.config(name), "peaks": loader.peaks("TPU v5 lite")}


@pytest.mark.parametrize("name", CONFIGS)
def test_readers_match_the_parent(name):
    trace = {"kernels": {"flash_attention": {"count": 2240, "s": 15.69}},
             "busy_s": 1, "window_s": 2}
    record = _record(name)
    for metric, want in READERS[name].items():
        assert loader.metric(metric).read(record, trace) == want, metric


@pytest.fixture(scope="module")
def smoke_weights():
    """name -> weights drawn at the smoke sizes, as a rehearsal draws them."""
    from repro.models import build_model
    from repro.models.diffusion import DiffusionLM

    out = {}
    for name in CONFIGS:
        cfg = loader.config(name)
        abstract = DiffusionLM(build_model(weights.program_config(cfg, True))).init_abstract()
        out[name] = weights.make_weights(abstract, WEIGHTS_SEED,
                                         cfg["denoiser"]["eps_head_gain"])
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_match_the_parent(name, smoke_weights):
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(smoke_weights[name])[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[name]


@pytest.mark.parametrize("name,precision", sorted(EPS))
def test_reference_eps_matches_the_parent(name, precision, smoke_weights):
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = _cfg(name, "smoke")
    x = jnp.asarray(reference.request_noise(2**31 + 9, 2, 64, cfg["hidden_size"]))
    fn = jax.jit(lambda p, x, t: reference.eps(p, x, t, cfg, precision))
    e = np.asarray(fn(smoke_weights[name], x, jnp.float32(0.37)))
    want_sha, want_sum = EPS[(name, precision)]
    assert float(np.sum(e.astype(np.float64))).hex() == want_sum
    assert hashlib.sha256(e.tobytes()).hexdigest() == want_sha


# ---- a new kind joins as files ----------------------------------------------

TOY = '''
"""A toy kind: a gated linear mix, and one attention call whose q/k and v
head dims differ (192 and 128)."""

import jax.numpy as jnp

from bench.reference import linear, rmsnorm

CALLS = []


def reference(p, x, cfg, precision):
    CALLS.append(tuple(x.shape))
    h = rmsnorm(p["norm"]["scale"], x, cfg["rms_norm_eps"])
    return x + p["gate"] * linear(p["proj"], h, precision)


def matmul_flops(cfg, rows, seq):
    return 2.0 * rows * seq * cfg["hidden_size"] ** 2


def flash_calls(cfg, rows, seq):
    return [(2.0 * rows * seq * seq * (192 + 128), 2.0 * rows * seq * (2 * 192 + 2 * 128))]


def program_keys(pcfg):
    return {"toy_width": pcfg}


def init_leaf(names, shape, key, gain):
    if names[-1] == "gate":
        return jnp.full(shape, 0.5, jnp.float32)
    raise ValueError(names)
'''

TOY_MAIN = '''
import json, sys
import jax, jax.numpy as jnp, numpy as np

from bench import flops, loader, reference, weights

D, T, ROWS, SEQ = 32, 16, 2, 8
cfg = loader.config("toy-mix")
f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
lin = lambda i, o, bias=True: dict(w=f32(1, i, o), **({"b": f32(1, o)} if bias else {}))
abstract = {
    "in_proj": {"w": f32(D, D)},
    "time_mlp": {"w1": {"w": f32(T, D), "b": f32(D)}, "w2": {"w": f32(D, D), "b": f32(D)}},
    "backbone": {"final_norm": {"scale": f32(D)}, "segs": {
        "0_toy": {"norm": {"scale": f32(1, D)}, "proj": lin(D, D, False), "gate": f32(1, D)},
        "1_dense": {"attn": {"wq": lin(D, 32), "wk": lin(D, 16), "wv": lin(D, 16),
                             "wo": lin(32, D, False)},
                    "ln1": {"scale": f32(1, D)}, "ln2": {"scale": f32(1, D)},
                    "mlp": {"wg": lin(D, 64, False), "wi": lin(D, 64, False),
                            "wo": lin(64, D, False)}}}},
    "eps_head": {"w": f32(D, D), "b": f32(D)},
}
params = weights.make_weights(abstract, 2**31 + 3, cfg["denoiser"]["eps_head_gain"])
x = jnp.asarray(reference.request_noise(11, ROWS, SEQ, D))
e = reference.eps(params, x, jnp.float32(0.5), cfg)
toy = loader.layer("toy")
out = {
    "bench": loader.BENCH,
    "gate": np.asarray(params["backbone"]["segs"]["0_toy"]["gate"]).tolist(),
    "eps_shape": list(e.shape), "eps_finite": bool(np.all(np.isfinite(np.asarray(e)))),
    "toy_calls": toy.CALLS,
    "forward_flops": flops.forward_flops(cfg, ROWS, SEQ),
    "dense_flops": loader.layer("dense").matmul_flops(cfg, ROWS, SEQ),
    "flash_work": flops.flash_work(cfg, ROWS, SEQ),
    "dense_flash": flops.flash_attention_call(cfg, ROWS, SEQ, 0),
    "program_keys": {k: m.program_keys(7) for k, m in loader.layers(cfg).items()},
    "program_imported": any(m.split(".")[0] == "repro" for m in sys.modules),
}
print(json.dumps(out))
'''

TOY_CONFIG = {
    "name": "toy-mix", "hidden_size": 32, "intermediate_size": 64,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "attn_window_size": 0,
    "layer_types": [["toy", 1], ["dense", 1]],
    "denoiser": {"time_embed_dim": 16, "eps_head_gain": 0.01},
}


def _bench_copy(tmp_path):
    shutil.copytree(loader.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "bench"


def test_a_new_kind_joins_as_files(tmp_path):
    bench = _bench_copy(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "layers" / "toy.py").write_text(textwrap.dedent(TOY))
    (bench / "configs" / "toy-mix.json").write_text(json.dumps(TOY_CONFIG))
    (tmp_path / "toy_main.py").write_text(TOY_MAIN)
    proc = benchproc.run([str(tmp_path / "toy_main.py")], tmp_path / "cache", cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    assert out["bench"] == str(bench)
    assert {p: p.read_bytes() for p in before} == before      # no file touched
    assert out["gate"] == [[0.5] * 32]                        # toy.init_leaf
    assert out["eps_shape"] == [2, 8, 32] and out["eps_finite"]
    assert out["toy_calls"] == [[2, 8, 32]]                   # toy.reference ran
    rows, seq, d, t = 2, 8, 32, 16
    common = 2.0 * rows * seq * 2 * d * d + 2.0 * rows * (t * d + d * d)
    assert out["forward_flops"] == common + 2.0 * rows * seq * d * d + out["dense_flops"]
    toy_call = [2.0 * rows * seq * seq * 320, 2.0 * rows * seq * 640, 1]
    assert out["flash_work"] == [toy_call, [*out["dense_flash"], 1]]
    assert out["program_keys"] == {"toy": {"toy_width": 7}, "dense": {}}
    assert out["program_imported"] is False


def test_an_unknown_kind_names_its_missing_file():
    cfg = dict(_cfg("qwen2-1.5b", "smoke"), layer_types=[["dense", 1], ["no_such_kind", 1]])
    want = os.path.join("bench", "layers", "no_such_kind.py")
    for call in (lambda: loader.layer("no_such_kind"), lambda: loader.layers(cfg),
                 lambda: flops.forward_flops(cfg, 2, 16), lambda: flops.flash_work(cfg, 2, 16)):
        with pytest.raises(SystemExit, match=want):
            call()


def test_an_unknown_kind_stops_the_run_before_any_weights(tmp_path, monkeypatch):
    for sub in ("workloads", "configs", "traffic"):
        (tmp_path / sub).mkdir()
    w = loader.workload("qwen2-1.5b.offline")
    (tmp_path / "workloads" / "x.json").write_text(json.dumps(dict(w, config="c")))
    cfg = loader.config("qwen2-1.5b")
    cfg = dict(cfg, smoke=dict(cfg["smoke"], layer_types=[["mla_moe", 2]]))
    (tmp_path / "configs" / "c.json").write_text(json.dumps(cfg))
    shutil.copy(os.path.join(loader.BENCH, "traffic", w["traffic"] + ".json"),
                tmp_path / "traffic")

    def refuse(*a, **kw):
        raise AssertionError("reached past the layer lookup")

    monkeypatch.setattr(loader, "BENCH", str(tmp_path))
    monkeypatch.setattr(harness, "check_devices", refuse)
    monkeypatch.setattr(weights, "make_weights", refuse)
    with pytest.raises(SystemExit, match="mla_moe.py"):
        harness.measure("x", 1, 1.0, False, rehearse=True)


# ---- the program is held to each kind's widths ------------------------------

MAMBA_KEYS = ["mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"]


@pytest.mark.parametrize("rehearse", [False, True], ids=["full", "smoke"])
@pytest.mark.parametrize("key", MAMBA_KEYS)
def test_a_lowered_mamba_width_is_refused(key, rehearse, tmp_path):
    path = tmp_path / "hymba-1.5b.json"
    shutil.copy(os.path.join(loader.BENCH, "configs", "hymba-1.5b.json"), path)
    cfg = json.loads(path.read_text())
    weights.program_config(cfg, rehearse)                     # as it stands: held
    section = cfg["smoke"] if rehearse else cfg
    section[key] = (cfg["smoke"] if rehearse and key in cfg["smoke"] else cfg)[key] // 2
    with pytest.raises(SystemExit, match=key):
        weights.program_config(cfg, rehearse)


def test_the_mamba_widths_are_held_by_the_hymba_modules(monkeypatch):
    cfg = loader.config("hymba-1.5b")
    cfg = dict(cfg, mamba_d_state=cfg["mamba_d_state"] // 2)
    for kind in ("hymba_full", "hymba_swa"):
        monkeypatch.setattr(loader.layer(kind), "program_keys", lambda pcfg: {})
    weights.program_config(cfg, False)                        # nothing holds it now
