"""The denoiser the benchmark serves: the program's model at the widths of
``bench/configs/<config>.json``, with weights the benchmark draws itself.

The weights come from ``--seed`` in one jitted call on the device, in the
type they are served in (float32 parameters; the program computes in
bfloat16).  Leaves are drawn by name: norms and the Mamba skip ``D`` at
one, biases at zero, ``A_log`` normal(0, 0.5), the depthwise conv
normal(0, 0.1), every other matrix normal(0, 1/fan_in) with fan_in its
input width, and the eps head at ``eps_head_gain`` (a random-weight sampler
is chaotic with a larger head).  The embedding table (and Hymba's meta
tokens) are not on the denoiser's path and are left at zero.  A leaf that
none of these rules covers is drawn by its layer kind's ``init_leaf``
(``bench/layers``), where the kind gives one.

``program_config`` holds the program to the configuration file's widths:
the keys compared here, and each layer kind's ``program_keys``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import loader

UNUSED = ("embed", "meta", "lm_head")


def program_config(bench_cfg: dict, rehearse: bool):
    """The program's ModelConfig for this configuration, checked against
    the benchmark's file so that no change to the program can shrink what
    the benchmark runs."""
    from repro.configs import get_config

    cfg = get_config(bench_cfg["program_config"])
    if rehearse:
        # the smoke preset computes in float32; keep the served bfloat16
        cfg = cfg.smoke().with_(dtype=cfg.dtype)
    want = dict(bench_cfg, **bench_cfg["smoke"]) if rehearse else bench_cfg
    blocks = [list(b) for b in cfg.blocks]
    got = {
        "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim,
        "layer_types": blocks,
        "attn_window_size": cfg.sliding_window,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "qkv_bias": cfg.qkv_bias,
        "hidden_act": cfg.mlp_act,
        "compute_dtype": jnp.dtype(cfg.dtype).name,
        "param_dtype": jnp.dtype(cfg.param_dtype).name,
    }
    for mod in loader.layers(want).values():
        got.update(mod.program_keys(cfg))
    want = dict(want, **{k: want["denoiser"][k] for k in ("compute_dtype", "param_dtype")})
    wrong = {k: (v, want.get(k)) for k, v in got.items() if v != want.get(k)}
    if wrong:
        raise SystemExit(f"program config differs from the benchmark's: {wrong}")
    return cfg


def _leaf_path(path) -> tuple[str, ...]:
    return tuple(str(getattr(p, "key", p)) for p in path)


def _init_leaf(names, shape, key, gain):
    last = names[-1]
    if names[0] == "eps_head":
        scale = gain / np.sqrt(shape[0]) if last == "w" else gain
        return jax.random.normal(key, shape, jnp.float32) * scale
    if any(n in UNUSED for n in names):
        return jnp.zeros(shape, jnp.float32)
    if last in ("scale", "D"):
        return jnp.ones(shape, jnp.float32)
    if last == "b":
        return jnp.zeros(shape, jnp.float32)
    if last == "A_log":
        return jax.random.normal(key, shape, jnp.float32) * 0.5
    if names[-2:] == ("conv", "w"):
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    if last == "w":
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])
    if names[:2] == ("backbone", "segs"):
        mod = loader.layer(names[2].split("_", 1)[1])       # segment "<i>_<kind>"
        if hasattr(mod, "init_leaf"):
            return mod.init_leaf(names, shape, key, gain)
    raise ValueError(f"no rule for parameter {'/'.join(names)}")


def make_weights(abstract, seed: int, gain: float, sharding=None):
    """Every parameter of ``abstract`` (the program's parameter shapes),
    drawn from ``seed`` in one jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = [(_leaf_path(p), tuple(a.shape)) for p, a in paths]
    for _, a in paths:
        if jnp.dtype(a.dtype) != jnp.float32:
            raise SystemExit(f"parameters served as {a.dtype}, expected float32")

    def draw(key):
        leaves = [
            _init_leaf(names, shape, jax.random.fold_in(key, i), gain)
            for i, (names, shape) in enumerate(specs)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    return jax.jit(draw, out_shardings=sharding)(key)
