"""A dense decoder layer (Qwen2), run bidirectionally.

Pre-norm GQA attention with rotate-half RoPE (q/k/v biases where the
program's parameters have them), then a pre-norm SwiGLU MLP, each added to
the residual.  Departure from the published (causal) layer: every position
attends to every other, as the denoiser runs it.
"""

from __future__ import annotations

from bench import flops
from bench.reference import attention, mlp, rmsnorm


def reference(p, x, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + attention(p["attn"], rmsnorm(p["ln1"]["scale"], x, eps), cfg, 0, precision)
    return x + mlp(p["mlp"], rmsnorm(p["ln2"]["scale"], x, eps), precision)


def matmul_flops(cfg, rows, seq):
    return flops.attention_flops(cfg, rows, seq, 0) + flops.mlp_flops(cfg, rows, seq)


def flash_calls(cfg, rows, seq):
    return [flops.flash_attention_call(cfg, rows, seq, 0)]


def program_keys(pcfg):
    return {}
