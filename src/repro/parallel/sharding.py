"""Logical sharding rules: pytree path + shape -> PartitionSpec.

Axis convention (DESIGN.md §5):
  * batch-like dims        -> the data axes ("pod","data") / ("data",)
  * heads / d_ff / vocab   -> "model" (tensor parallel), guarded by
                              divisibility — non-divisible dims (e.g. 25
                              Hymba heads, 8 Mixtral KV heads on tp=16)
                              replicate, which is the production reality of
                              KV-replicated GQA tensor parallelism
  * experts                -> "model" when expert count divides (DeepSeek
                              64/16 -> expert parallel); else expert FFN dim
  * layer-stacked leading dim (inside "segs/") -> never sharded (scanned)

Everything is derived from path strings over the spec tree, so the same
rules shard real params, abstract params, optimizer mirrors, and caches.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def dp_size(mesh: Mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def round_to_dp(n: int, mesh: Mesh | None) -> int:
    """Smallest multiple of the mesh's data-parallel size that is >= n.

    The serving engine rounds batch buckets with this so every fused batch
    splits evenly across the data axes (no ragged shards)."""
    if mesh is None:
        return n
    dp = dp_size(mesh)
    return -(-n // dp) * dp


class SamplerSpecs(NamedTuple):
    """PartitionSpecs for a solver program's sampling scan carry.

    The field set covers the union of the registry programs' carries: the
    latents ``x`` (batch-leading, every solver), the eps history ``eps_buf``
    ``(cap, B, ...)`` — batch is axis 1, like KV caches — and replicated
    ``t_buf`` time grid (ERA / Adams-family history buffers), and the
    per-sample solver state ``delta_eps`` ((B,) for per-sample ERS, scalar
    otherwise).  ``lengths`` places the mixed-seq-len path's per-row (B,)
    valid-length vector batch-aligned with its rows, so the masked error
    norms stay shard-local.  ``active_steps`` / ``step_ts`` are the
    mixed-NFE path's :class:`~repro.core.program.StepMask` channel: the
    per-row (B,) step counts and (B, n_steps + 1) per-row time grids shard
    batch-aligned with their rows, so each shard reads only its own rows'
    grids and activity.  Programs read the fields their carry uses and
    ignore the rest (DDIM touches only ``x``; DPM++(2M)'s ``x0_prev``
    shards like ``x``).
    """

    x: P
    eps_buf: P
    t_buf: P
    delta_eps: P
    lengths: P
    active_steps: P
    step_ts: P


class SamplerShardings(NamedTuple):
    """``SamplerSpecs`` bound to a concrete mesh (NamedSharding leaves)."""

    x: NamedSharding
    eps_buf: NamedSharding
    t_buf: NamedSharding
    delta_eps: NamedSharding
    lengths: NamedSharding
    active_steps: NamedSharding
    step_ts: NamedSharding


def sampler_pspecs(
    mesh: Mesh,
    *,
    batch: int | None = None,
    per_sample: bool = True,
    x_ndim: int = 3,
) -> SamplerSpecs:
    """Scan-carry PartitionSpecs for the batched sampling engine.

    Everything shards only along the batch dimension over the mesh's data
    axes; per-sample ERS then keeps the whole solver loop collective-free
    (each shard measures its own rows' delta_eps and selects its own
    Lagrange bases).  If ``batch`` is given and does not divide the
    data-parallel size, every entry degrades to replicated — correct, just
    not parallel — so exact-size (unpadded) runs never hit a ragged-shard
    jit error.
    """
    dp: Any = data_axes(mesh)
    if not dp or (batch is not None and not _div(batch, dp_size(mesh))):
        dp = None
    rest = (None,) * (x_ndim - 1)
    return SamplerSpecs(
        x=P(dp, *rest),
        eps_buf=P(None, dp, *rest),
        t_buf=P(),
        delta_eps=P(dp) if per_sample else P(),
        lengths=P(dp),
        active_steps=P(dp),
        step_ts=P(dp, None),
    )


def sampler_shardings(
    mesh: Mesh,
    *,
    batch: int | None = None,
    per_sample: bool = True,
    x_ndim: int = 3,
) -> SamplerShardings:
    """``sampler_pspecs`` materialized as NamedShardings on ``mesh`` (what
    a program's ``sample_scan`` takes as its ``shardings`` argument)."""
    specs = sampler_pspecs(
        mesh, batch=batch, per_sample=per_sample, x_ndim=x_ndim
    )
    return SamplerShardings(*(NamedSharding(mesh, s) for s in specs))


def solver_carry_pspecs(
    mesh: Mesh,
    program,
    config,
    *,
    batch: int | None = None,
    x_ndim: int = 3,
) -> SamplerSpecs:
    """Carry PartitionSpecs for a :class:`repro.core.SolverProgram`.

    The program declares whether its carry holds per-sample ``(B,)`` solver
    state (``per_sample_state(cfg)``); everything else follows the shared
    batch-over-data-axes layout of :func:`sampler_pspecs`."""
    return sampler_pspecs(
        mesh,
        batch=batch,
        per_sample=program.per_sample_state(config),
        x_ndim=x_ndim,
    )


def solver_carry_shardings(
    mesh: Mesh,
    program,
    config,
    *,
    batch: int | None = None,
    x_ndim: int = 3,
) -> SamplerShardings:
    """:func:`solver_carry_pspecs` bound to ``mesh`` as NamedShardings."""
    return sampler_shardings(
        mesh,
        batch=batch,
        per_sample=program.per_sample_state(config),
        x_ndim=x_ndim,
    )


def per_batch_shard(rows: NamedSharding, fn, *args, batch_dims):
    """``fn(*args)`` run once per shard of the batch under ``shard_map``.

    ``rows`` is a batch-leading sharding (a carry's ``x``): its first spec
    entry names the mesh axes the batch is split over, or None when the
    batch is replicated.  ``batch_dims[i]`` is the batch axis of
    ``args[i]``, None for an argument every shard sees whole (any pytree).
    Every output is batch-leading.  XLA cannot partition a Mosaic kernel,
    so a mesh program that runs Pallas kernels runs them this way; ``fn``
    must be row-local."""
    axes = rows.spec[0] if len(rows.spec) else None
    in_specs = tuple(
        P() if d is None else P(*([None] * d), axes) for d in batch_dims
    )
    # check_vma off: pallas_call's out_shape carries no varying-axes type
    return jax.shard_map(
        fn, mesh=rows.mesh, in_specs=in_specs, out_specs=P(axes),
        check_vma=False,
    )(*args)


class ParamReplicator:
    """Replicate a params tree over a mesh, caching the placed copy.

    The cache key is the identity of every leaf, not of the container —
    callers that rebuild or mutate their params dict between calls (a
    finetune-and-sample loop) get a fresh placement instead of silently
    sampling with the first call's weights."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._sharding = NamedSharding(mesh, P())
        # the cached leaves are held alongside their ids: id() values are
        # only unique among live objects, so pinning the leaves is what
        # makes the identity key trustworthy across caller-side rebuilds
        self._cached_leaves: list | None = None
        self._placed: Any = None

    @property
    def sharding(self) -> NamedSharding:
        """The fully-replicated placement every leaf is committed to —
        what an AOT caller attaches to its params avals so the compiled
        program accepts replicated leaves without resharding."""
        return self._sharding

    def __call__(self, params):
        leaves = jax.tree.leaves(params)
        stale = (
            self._cached_leaves is None
            or len(leaves) != len(self._cached_leaves)
            or any(a is not b for a, b in zip(leaves, self._cached_leaves))
        )
        if stale:
            self._placed = jax.tree.map(
                lambda a: jax.device_put(a, self._sharding), params
            )
            self._cached_leaves = leaves
        return self._placed


class ShardingRules:
    """fsdp=True additionally shards each large parameter's biggest
    unsharded dim over the "data" axis (ZeRO-3 / MaxText fsdp style) —
    required for the 67B-class train_4k combos to fit 16 GB HBM."""

    def __init__(self, cfg: ModelConfig, mesh: Mesh, fsdp: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.tp = tp_size(mesh)
        self.dp = data_axes(mesh)
        self.fsdp = fsdp
        self.fsdp_axis = "data" if "data" in mesh.axis_names else None
        self.fsdp_size = mesh.shape.get("data", 1)

    # -- parameter rules ---------------------------------------------------
    def _param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        tp, cfg = self.tp, self.cfg
        mdl = "model"

        def out_col(ncols):  # shard a (in, out) matmul's out dim
            return P(None, mdl) if _div(ncols, tp) else P(None, None)

        def in_row(nrows):   # shard a (in, out) matmul's in dim
            return P(mdl, None) if _div(nrows, tp) else P(None, None)

        leaf = path.rsplit("/", 1)[-1]
        if path.endswith("embed") or path == "embed":
            return P(mdl, None) if _div(shape[0], tp) else P(None, None)
        if "pos_embed" in path:
            return P(mdl, None) if _div(shape[0], tp) else P(None, None)
        if "lm_head" in path:
            return out_col(shape[-1])
        if "meta" in path:
            return P(None, None)

        # xLSTM blocks: per-head recurrent math with nh << tp; replicate
        # (the arch is small — data parallel carries it; see DESIGN.md)
        if "mlstm" in path or "slstm" in path:
            return P(*([None] * len(shape)))

        if "experts" in path and len(shape) == 3:
            e, a, b = shape
            if _div(e, tp):
                return P(mdl, None, None)        # expert parallel
            # tensor-parallel experts: shard the ff dim
            if path.endswith("wo"):              # (E, ff, d)
                return P(None, mdl, None) if _div(a, tp) else P(None, None, None)
            return P(None, None, mdl) if _div(b, tp) else P(None, None, None)
        if "router" in path:
            return P(None, None)

        if any(s in path for s in ("/attn/", "self_attn", "cross_attn", "/mla/")):
            if leaf == "b":
                return P(mdl) if _div(shape[0], tp) else P(None)
            if any(path.endswith(s) for s in ("wq/w", "wk/w", "wv/w", "wkv_b/w")):
                return out_col(shape[-1])
            if path.endswith("wo/w"):
                return in_row(shape[0])
            return P(*([None] * len(shape)))     # wkv_a, norms

        if "mamba" in path:
            if path.endswith("in_proj/w"):
                return out_col(shape[-1])
            if path.endswith("out_proj/w"):
                return in_row(shape[0])
            if leaf == "A_log" or leaf == "D":
                return (
                    P(mdl, None) if len(shape) == 2 and _div(shape[0], tp)
                    else (P(mdl) if _div(shape[0], tp) else P(*([None] * len(shape))))
                )
            if path.endswith("x_proj/w") or path.endswith("dt_proj/w"):
                return in_row(shape[0])
            if path.endswith("dt_proj/b"):
                return P(mdl) if _div(shape[0], tp) else P(None)
            if "conv" in path:
                return (
                    P(None, mdl) if len(shape) == 2 and _div(shape[-1], tp)
                    else (P(mdl) if _div(shape[0], tp) else P(None))
                )
            return P(*([None] * len(shape)))

        if "mlp" in path or "shared" in path:
            if leaf == "b":
                return P(mdl) if _div(shape[0], tp) else P(None)
            if path.endswith("wo/w"):
                return in_row(shape[0])
            return out_col(shape[-1])

        return P(*([None] * len(shape)))

    def _apply_fsdp(self, spec: P, shape: tuple[int, ...]) -> P:
        import math
        if (
            not self.fsdp
            or self.fsdp_axis is None
            or math.prod(shape) < (1 << 20)
        ):
            return spec
        parts = list(spec) + [None] * (len(shape) - len(spec))
        # biggest unsharded dim divisible by the data axis
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if parts[i] is None and _div(shape[i], self.fsdp_size):
                parts[i] = self.fsdp_axis
                break
        return P(*parts)

    def param_pspec(self, tree) -> Any:
        """PartitionSpecs for a (spec/abstract/real) param tree."""

        def visit(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            shape = tuple(leaf.shape)
            # embedding tables are gathered by token id — FSDP-sharding their
            # feature dim forces SPMD into full rematerialization
            fsdp_ok = "embed" not in pstr
            if "segs/" in pstr or pstr.startswith("segs"):
                inner = self._param_spec(pstr, shape[1:])
                if fsdp_ok:
                    inner = self._apply_fsdp(inner, shape[1:])
                return P(None, *inner)
            spec = self._param_spec(pstr, shape)
            return self._apply_fsdp(spec, shape) if fsdp_ok else spec

        return jax.tree_util.tree_map_with_path(visit, tree)

    def param_sharding(self, tree) -> Any:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.param_pspec(tree)
        )

    # -- optimizer state mirrors the params ---------------------------------
    def opt_sharding(self, opt_tree) -> Any:
        pspec = {
            "m": self.param_pspec(opt_tree["m"]),
            "v": self.param_pspec(opt_tree["v"]),
            "step": P(),
        }
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), pspec)

    # -- batch / cache -------------------------------------------------------
    def _dp_if_divisible(self, n: int):
        total = 1
        for a in self.dp:
            total *= self.mesh.shape[a]
        return self.dp if _div(n, total) else None

    def batch_sharding(self, batch_tree) -> Any:
        def visit(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            shape = tuple(leaf.shape)
            if len(shape) == 0:
                return NamedSharding(self.mesh, P())
            dp = self._dp_if_divisible(shape[0])
            rest = [None] * (len(shape) - 1)
            return NamedSharding(self.mesh, P(dp, *rest))

        return jax.tree_util.tree_map_with_path(visit, batch_tree)

    def cache_sharding(self, cache_tree) -> Any:
        """Caches: (L, B, slots, ...) -> batch over data axes; large slot
        dims over "model" (kv heads < tp for every assigned arch, so
        sequence-sharding the cache is what bounds decode memory)."""

        def visit(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            shape = tuple(leaf.shape)
            if len(shape) <= 2:  # (L, slots) position arrays etc.
                return NamedSharding(self.mesh, P(*([None] * len(shape))))
            dp = self._dp_if_divisible(shape[1])
            rest = [None] * (len(shape) - 2)
            # k/v/ckv caches: (L, B, slots, ...) — shard big slot dims
            if len(shape) >= 4 and shape[2] >= 4096 and _div(shape[2], self.tp):
                rest[0] = "model"
            return NamedSharding(self.mesh, P(None, dp, *rest))

        return jax.tree_util.tree_map_with_path(visit, cache_tree)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    return str(k)
