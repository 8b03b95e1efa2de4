"""Operations and bytes the sampler's work needs, from a configuration's
shapes (``bench/configs``).  These are what a call requires, not what the
program happens to compute: padding (rows, positions, head dims) and
re-reads are not counted.

Counted: every matrix product of the denoiser (2 flops per multiply-add):
in_proj, the time MLP, attention projections, the score and value products
of attention over the (query, key) pairs its mask admits, the MLP, the
Mamba heads' projections and depthwise conv, and the eps head.  Left out:
norms, RoPE, softmax, activations, and the Mamba selective scan's
elementwise recurrence (about 6 flops per state element per position),
which runs on the vector units and has no matrix-unit peak to compare with.
"""

from __future__ import annotations


def attention_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a bidirectional mask admits: all of them, or with
    a window, keys with |q - k| < window (``bench/reference.py``)."""
    if window <= 0 or window >= seq:
        return seq * seq
    # query q sees keys max(0, q - window + 1) .. min(seq - 1, q + window - 1)
    return sum(min(seq, q + window) - max(0, q - window + 1) for q in range(seq))


def _layer_matmul_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    nh, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * (nh * hd) + 2 * d * (kv * hd) + (nh * hd) * d
    mlp = 3 * d * cfg["intermediate_size"]
    if kind == "dense":
        return attn + mlp
    di = cfg["mamba_expand"] * d
    n, dtr = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    mamba = d * 2 * di + di * (dtr + 2 * n) + dtr * di + di * d
    return attn + mlp + mamba + cfg["mamba_d_conv"] * di


def forward_flops(cfg: dict, rows: int, seq: int) -> float:
    """One denoiser forward (one NFE) over ``rows`` samples of ``seq``
    positions."""
    d = cfg["hidden_size"]
    t = cfg["denoiser"]["time_embed_dim"]
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    tokens = rows * seq
    total = 2.0 * tokens * 2 * d * d                 # in_proj + eps head
    total += 2.0 * rows * (t * d + d * d)            # time MLP
    for kind, count in cfg["layer_types"]:
        window = cfg["attn_window_size"] if kind.endswith("_swa") else 0
        total += count * 2.0 * tokens * _layer_matmul_params(cfg, kind)
        total += count * 4.0 * rows * nh * hd * attention_pairs(seq, window)
    return total


def flash_attention_call(cfg: dict, rows: int, seq: int, window: int,
                         dtype_bytes: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one flash-attention call over one layer: q and
    the output at every head, k and v at every kv head, each read or
    written once."""
    nh, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 4.0 * rows * nh * hd * attention_pairs(seq, window)
    nbytes = float(dtype_bytes) * rows * seq * hd * (2 * nh + 2 * kv)
    return flops, nbytes


def flash_calls_per_nfe(cfg: dict) -> list[tuple[int, int]]:
    """(window, layer count) of the attention calls of one forward."""
    out = []
    for kind, count in cfg["layer_types"]:
        out.append((cfg["attn_window_size"] if kind.endswith("_swa") else 0, count))
    return out
