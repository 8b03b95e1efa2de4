"""DeepSeek-V2's leading dense layer (``first_k_dense_replace``), run
bidirectionally: the MLA of ``mla_moe`` (whose code it uses, with its
departures), then a pre-norm SwiGLU MLP of ``intermediate_size``, each
added to the residual.
"""

from __future__ import annotations

from bench import flops, loader
from bench.reference import mlp, rmsnorm

moe_layer = loader.layer("mla_moe")


def reference(p, x, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + moe_layer.mla(p["mla"], rmsnorm(p["ln1"]["scale"], x, eps), cfg, precision)
    return x + mlp(p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps), precision)


def matmul_flops(cfg, rows, seq):
    return moe_layer.mla_flops(cfg, rows, seq) + flops.mlp_flops(cfg, rows, seq)


flash_calls = moe_layer.flash_calls
program_keys = moe_layer.mla_keys
