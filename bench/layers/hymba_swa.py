"""A Hymba layer with sliding-window attention, run bidirectionally.

The layer of ``hymba_full`` (whose code it uses) with attention over a
window of ``attn_window_size``.  Departure from the published (causal)
layer, besides ``hymba_full``'s: the published window sees the
``window`` positions up to a query's own; run bidirectionally it sees them
on both sides, keys with ``|q - k| < window``.
"""

from __future__ import annotations

from bench import flops, loader

full = loader.layer("hymba_full")
program_keys = full.program_keys


def reference(p, x, cfg, precision):
    return full.layer(p, x, cfg, cfg["attn_window_size"], precision)


def matmul_flops(cfg, rows, seq):
    return full.layer_flops(cfg, rows, seq, cfg["attn_window_size"])


def flash_calls(cfg, rows, seq):
    return [flops.flash_attention_call(cfg, rows, seq, cfg["attn_window_size"])]
