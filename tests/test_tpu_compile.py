"""Compile-only walls: the main path's Pallas kernels lower and compile for a
described TPU v5e chip at qwen2-1.5b widths (the selective scan at
hymba-1.5b's, MLA's flash call and the dropless experts at
deepseek-v2-lite's), with no chip attached.

Nothing runs here; the TPU compiler (installed with libtpu) compiles for
the described device and refuses what the chip would refuse: misaligned
block shapes, illegal operand layouts, too much VMEM.  Each test asserts
that the compiled program holds the Mosaic kernel (``tpu_custom_call``),
so an interpret-mode or XLA fallback cannot pass for the kernel.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.era import AM4
from repro.kernels import era_update as era_kernel
from repro.kernels import flash_attention as flash_kernel
from repro.kernels import ops

# qwen2-1.5b attention and latent widths, serving batch 8 at seq 512
B, H, KV, S, HD, D = 8, 12, 2, 512, 128, 1536
K_ORDER = 4
# hymba-1.5b's Mamba heads: d_inner 3200, state 16
DI, N_STATE = 3200, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


def _era_avals(one_chip, lead=()):
    n = S * D
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    return (
        sds(lead + (n,)),
        sds(lead + (K_ORDER, n)),
        sds(lead + (K_ORDER,)),
        sds(lead + (3, n)),
    )


def _era_call(x, eps_sel, lag_w, e_hist):
    am4 = jnp.asarray(AM4, jnp.float32)
    return era_kernel.era_update(
        x, eps_sel, lag_w, e_hist, am4, jnp.float32(0.9), jnp.float32(-0.1)
    )


def test_era_update_compiles_for_v5e(one_chip):
    text = _compiled_text(_era_call, *_era_avals(one_chip))
    assert "tpu_custom_call" in text


def test_era_update_per_sample_vmap_compiles_for_v5e(one_chip):
    """Per-sample ERS vmaps the kernel over the batch: each row carries its
    own Lagrange weights, and the batching rule adds a grid axis."""
    text = _compiled_text(jax.vmap(_era_call), *_era_avals(one_chip, (B,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "masked, dtype",
    [(False, jnp.bfloat16), (True, jnp.bfloat16), (True, jnp.float32)],
    ids=["unmasked", "masked", "masked-f32"],
)
def test_flash_attention_compiles_for_v5e(one_chip, masked, dtype):
    """bf16 is the served dtype; float32 is the chip check's twin engine."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((B, H, S, HD), dtype)
    kv = sds((B, KV, S, HD), dtype)
    pos = sds((S,), jnp.int32)
    mask = sds((B, S), jnp.int32)

    def call(q, k, v, pos, mask=None):
        return flash_kernel.flash_attention(
            q, k, v, pos, pos, causal=False, kv_mask=mask
        )

    avals = (q, kv, kv, pos) + ((mask,) if masked else ())
    assert "tpu_custom_call" in _compiled_text(call, *avals)


def test_mla_flash_attention_compiles_for_v5e(one_chip, monkeypatch):
    """MLA's call at deepseek-v2-lite's widths: 16 heads, q/k head dim 192
    and v padded up to it (``models/mla.py``), both padded to 256 here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 1024, 16, 192), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)

    def call(q, k, v, pos):
        return ops.flash_attention(q, k, v, pos, pos, causal=False)

    assert "tpu_custom_call" in _compiled_text(call, q, q, q, pos)


# each benchmark cell's flash call in the model layout: rows, seq, q heads,
# kv heads, head dim, window, dtype (float32: the chip check's twin engine)
CELL_FLASH_CALLS = {
    "qwen2-1.5b": (16, 1024, 12, 2, 128, 0, jnp.bfloat16),
    "qwen2-1.5b-f32": (16, 1024, 12, 2, 128, 0, jnp.float32),
    "hymba-1.5b": (4, 1024, 25, 5, 64, 1024, jnp.bfloat16),
    "deepseek-v2-lite": (16, 1024, 16, 16, 192, 0, jnp.bfloat16),
}


@pytest.mark.parametrize("call", CELL_FLASH_CALLS.values(), ids=list(CELL_FLASH_CALLS))
def test_flash_attention_compiles_at_the_cells_calls_for_v5e(
    one_chip, monkeypatch, call
):
    """Through the wrapper, so at the tiles the call's shape picks (512-row
    q tiles, the whole 1024 keys in one block): a tile past the chip's VMEM
    fails here, not on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, seq, h, kv, hd, window, dtype = call
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def attend(q, k, v, pos, mask):
        return ops.flash_attention(
            q, k, v, pos, pos, kv_mask=mask, causal=False, window=window
        )

    text = _compiled_text(
        attend,
        sds((rows, seq, h, hd), dtype),
        sds((rows, seq, kv, hd), dtype),
        sds((rows, seq, kv, hd), dtype),
        sds((seq,), jnp.int32),
        sds((rows, seq), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_dropless_experts_compile_for_v5e(one_chip):
    """The held-experts layer at the deepseek-v2-lite.offline cell's shape
    (16 x 1024 tokens, 8 of 64 experts of 1408 held, top-6, 2 shared):
    its products compile to the chip's own ragged dot, not to the masked
    dense contraction that other platforms lower ``ragged_dot`` to."""
    from repro.configs import get_config
    from repro.models import layers as L
    from repro.models.moe import moe_ffn, moe_specs

    cfg = get_config("deepseek-v2-lite-16b-ep8")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        L.abstract_params(moe_specs(cfg)),
    )
    x = jax.ShapeDtypeStruct((16, 1024, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(lambda p, x: moe_ffn(p, x, cfg)[0], params, x)
    assert text.count("ragged-dot") >= 3


def _scan_avals(sharding, rows, seq, dtype, rep=None):
    """dt, x, A, B, C, h0 as ``ops.selective_scan`` takes them."""
    sds = lambda shape, dt, sh=sharding: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    return (
        sds((rows, seq, DI), dtype),
        sds((rows, seq, DI), dtype),
        sds((DI, N_STATE), jnp.float32, rep or sharding),
        sds((rows, seq, N_STATE), dtype),
        sds((rows, seq, N_STATE), dtype),
        sds((rows, DI, N_STATE), jnp.float32),
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_selective_scan_compiles_for_v5e(one_chip, monkeypatch, dtype):
    """At the hymba-1.5b.offline cell's shapes: 4 rows of 1024 positions.
    bf16 is the served dtype; float32 is the chip check's twin engine."""
    # the wrapper asks the platform whether to interpret: answer as the
    # chip would, since the compile below is for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_text(ops.selective_scan, *_scan_avals(one_chip, 4, 1024, dtype))
    assert "tpu_custom_call" in text


def test_kernels_compile_per_batch_shard_on_a_v5e_mesh(topo, monkeypatch):
    """On a mesh XLA cannot partition a Mosaic kernel; run per batch shard
    (``per_batch_shard``, as the serving executor runs the denoiser, with
    its flash attention and selective scan, and ERA runs its step), the
    4-chip program compiles with the kernels in it and the batch rows
    spread over the chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.parallel.sharding import per_batch_shard

    # the wrappers ask the platform whether to interpret: answer as the
    # chip would, since the compile below is for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:4]), ("data",), axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def step(q, k, v, pos, mask, x, eps_sel, t_sel, e_hist, *scan_args):
        o = per_batch_shard(
            rows,
            lambda q, k, v, pos, mask: ops.flash_attention(
                q, k, v, pos, pos, causal=False, kv_mask=mask
            ),
            q, k, v, pos, mask, batch_dims=(0, 0, 0, None, 0),
        )
        am4 = jnp.asarray(AM4, jnp.float32)
        x_next, _ = per_batch_shard(
            rows,
            jax.vmap(
                lambda xb, es, ts, eh: ops.era_step(
                    xb, es, ts, eh, jnp.float32(0.1), jnp.float32(0.9),
                    jnp.float32(-0.1), am4,
                )
            ),
            x, eps_sel, t_sel, e_hist, batch_dims=(0, 0, 0, 0),
        )
        y, h_last = per_batch_shard(
            rows, ops.selective_scan, *scan_args,
            batch_dims=(0, 0, None, 0, 0, 0),
        )
        return o, x_next, y, h_last

    avals = (
        sds((B, S, H, HD), jnp.bfloat16, rows),
        sds((B, S, KV, HD), jnp.bfloat16, rows),
        sds((B, S, KV, HD), jnp.bfloat16, rows),
        sds((S,), jnp.int32, rep),
        sds((B, S), jnp.int32, rows),
        sds((B, S, D), jnp.float32, rows),
        sds((B, K_ORDER, S, D), jnp.float32, rows),
        sds((B, K_ORDER), jnp.float32, rows),
        sds((B, 3, S, D), jnp.float32, rows),
    ) + _scan_avals(rows, B, S, jnp.bfloat16, rep=rep)
    compiled = jax.jit(step).lower(*avals).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    x_rows = compiled.input_shardings[0][5].devices_indices_map((B, S, D))
    assert sorted(idx[0].start for idx in x_rows.values()) == [0, 2, 4, 6]
