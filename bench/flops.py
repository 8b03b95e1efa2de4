"""Operations and bytes the sampler's work needs, from a configuration's
shapes (``bench/configs``).  These are what a call requires, not what the
program happens to compute: padding (rows, positions, head dims) and
re-reads are not counted.

Counted: every matrix product of the denoiser (2 flops per multiply-add):
in_proj, the time MLP and the eps head here, and each layer's products in
its kind's module (``bench/layers/<kind>.py``: ``matmul_flops``), built on
the GQA attention and SwiGLU MLP counts here; attention's score and value
products are counted over the (query, key) pairs its mask admits.  Left
out: norms, RoPE, softmax, activations, and whatever a module names (the
Mamba selective scan's elementwise recurrence, about 6 flops per state
element per position, runs on the vector units and has no matrix-unit peak
to compare with).
"""

from __future__ import annotations

from bench import loader


def attention_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a bidirectional mask admits: all of them, or with
    a window, keys with |q - k| < window (``bench/reference.py``)."""
    if window <= 0 or window >= seq:
        return seq * seq
    # query q sees keys max(0, q - window + 1) .. min(seq - 1, q + window - 1)
    return sum(min(seq, q + window) - max(0, q - window + 1) for q in range(seq))


def attention_flops(cfg: dict, rows: int, seq: int, window: int) -> float:
    """GQA attention of one layer (``reference.attention``): the q, k, v
    and output projections, and the score and value products over the
    pairs the mask admits."""
    d = cfg["hidden_size"]
    nh, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    proj = d * (nh * hd) + 2 * d * (kv * hd) + (nh * hd) * d
    return 2.0 * rows * seq * proj + 4.0 * rows * nh * hd * attention_pairs(seq, window)


def mlp_flops(cfg: dict, rows: int, seq: int) -> float:
    """The SwiGLU MLP of one layer (``reference.mlp``)."""
    return 2.0 * rows * seq * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def forward_flops(cfg: dict, rows: int, seq: int) -> float:
    """One denoiser forward (one NFE) over ``rows`` samples of ``seq``
    positions."""
    d = cfg["hidden_size"]
    t = cfg["denoiser"]["time_embed_dim"]
    total = 2.0 * rows * seq * 2 * d * d             # in_proj + eps head
    total += 2.0 * rows * (t * d + d * d)            # time MLP
    for kind, count in cfg["layer_types"]:
        total += count * loader.layer(kind).matmul_flops(cfg, rows, seq)
    return total


def flash_attention_call(cfg: dict, rows: int, seq: int, window: int,
                         dtype_bytes: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one flash-attention call over one GQA layer: q
    and the output at every head, k and v at every kv head, each read or
    written once."""
    nh, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 4.0 * rows * nh * hd * attention_pairs(seq, window)
    nbytes = float(dtype_bytes) * rows * seq * hd * (2 * nh + 2 * kv)
    return flops, nbytes


def flash_work(cfg: dict, rows: int, seq: int) -> list[tuple[float, float, int]]:
    """(flops, bytes, layer count) of each flash-attention call of one
    forward, in layer order, as each kind's module counts it
    (``flash_calls``)."""
    return [
        (f, b, count)
        for kind, count in cfg["layer_types"]
        for f, b in loader.layer(kind).flash_calls(cfg, rows, seq)
    ]


def flash_calls_per_nfe(cfg: dict) -> list[tuple[int, int]]:
    """(window, layer count) of the GQA attention calls of one forward:
    a kind named ``*_swa`` attends over ``attn_window_size``."""
    out = []
    for kind, count in cfg["layer_types"]:
        out.append((cfg["attn_window_size"] if kind.endswith("_swa") else 0, count))
    return out
