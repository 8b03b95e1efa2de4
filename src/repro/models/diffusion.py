"""Diffusion-LM wrapper: any backbone becomes an eps-prediction denoiser.

This is how the paper's solver integrates with the assigned architectures
(DESIGN.md §3): x_t lives in embedding space (B, S, d); the wrapper adds
sinusoidal-time conditioning, runs the backbone stack (non-causal where the
family supports it), and projects to a noise estimate.  Each NFE of an
ERA-Solver sampling run is exactly one backbone forward.

Training objective: Eq. 5 of the paper (simplified eps-matching loss).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.schedules import NoiseSchedule
from repro.models import layers as L
from repro.models.model import Model

Array = jax.Array

#: block kinds safe to run right-padded with per-row ``lengths``: a padded
#: row's valid positions compute exactly the unpadded run's math.  Two ways
#: a kind earns membership:
#:
#: * **maskable attention** — every cross-position mixing is an attention
#:   softmax that takes the per-row kv_mask (dense / moe / enc / hymba_* /
#:   mla_* attention halves, xdec self-attention): pad keys get an exact
#:   ``-1e30`` bias, valid keys an exact ``+0.0``.  All three SDPA impls
#:   (naive / chunked / pallas+banded flash kernels) carry the mask
#:   natively, so fused masked batches stay on the fast kernels.
#: * **directional scans** — SSM / recurrent kinds (mamba inside hymba_*,
#:   mlstm, slstm) mix positions strictly left-to-right, so right-padding
#:   can never reach a prefix position's output (prefix-safety wall:
#:   ``tests/test_prefix_safety.py``; see the contract note in
#:   :mod:`repro.models.ssm`).
#:
#: The pad tail itself is handled by :meth:`DiffusionLM.eps`, which zeroes
#: eps at pad positions so padded tails stay inert across a sampling run.
MASKABLE_BLOCKS = frozenset(
    {
        "dense", "moe", "enc", "xdec",
        "mlstm", "slstm", "hymba_swa", "hymba_full",
        "mla_dense", "mla_moe",
    }
)


def diffusion_specs(model: Model) -> dict:
    d = model.config.d_model
    return {
        "backbone": model.specs(),
        "time_mlp": L.time_mlp_specs(d),
        "in_proj": L.linear_specs(d, d),
        "eps_head": {"w": L.P((d, d), "zeros"), "b": L.P((d,), "zeros")},
    }


@dataclasses.dataclass(frozen=True)
class DiffusionLM:
    model: Model
    causal: bool = False  # attention families denoise bidirectionally

    @property
    def config(self):
        return self.model.config

    def specs(self) -> dict:
        return diffusion_specs(self.model)

    def init(self, key: jax.Array) -> dict:
        return L.init_params(self.specs(), key, self.config.param_dtype)

    def init_abstract(self) -> dict:
        return L.abstract_params(self.specs(), self.config.param_dtype)

    @property
    def supports_length_masking(self) -> bool:
        """Can this denoiser run right-padded mixed-seq-len batches such
        that every valid position's output is exactly the unpadded run's?
        True iff every block kind is in :data:`MASKABLE_BLOCKS` — maskable
        attention or a right-pad prefix-safe directional scan.  The serving
        engine consults this before seq-bucketing and falls back to
        exact-shape grouping otherwise (counted by
        ``sampler_masked_fallback_total``)."""
        return all(kind in MASKABLE_BLOCKS for kind, _ in self.config.blocks)

    def eps(
        self, params: dict, x_t: Array, t: Array,
        lengths: Array | None = None,
    ) -> Array:
        """Noise prediction eps_theta(x_t, t). x_t: (B, S, d); t a scalar
        shared by the batch, or per-row times shaped (B,) / (B, 1, 1)
        (mixed-NFE and adaptive solvers condition each row on its own
        time).

        ``lengths`` ((B,) int32) marks per-row right-padding: pad keys are
        masked out of every attention softmax (valid positions see exactly
        the unpadded batch's math) and the returned eps is zeroed at pad
        positions, so a padded row's tail stays inert and bounded across a
        whole sampling run instead of evolving garbage."""
        cfg = self.config
        tcond = L.time_mlp(params["time_mlp"], jnp.reshape(t, (-1,)))  # (1|B, d)
        h = L.linear(params["in_proj"], x_t.astype(cfg.dtype))
        h = h + tcond[:, None, :].astype(h.dtype)
        h, _ = self.model.backbone(
            params["backbone"], h, mode="train", causal=self.causal,
            lengths=lengths,
        )
        eps = h @ params["eps_head"]["w"].astype(h.dtype) + params["eps_head"][
            "b"
        ].astype(h.dtype)
        # zero-init head -> identity-ish residual from x_t at step 0
        out = (eps.astype(jnp.float32) + x_t.astype(jnp.float32)).astype(
            x_t.dtype
        )
        if lengths is not None:
            valid = jnp.arange(out.shape[1], dtype=jnp.int32) < lengths[:, None]
            out = jnp.where(valid[..., None], out, 0.0)
        return out

    def eps_fn(self, params: dict, lengths: Array | None = None):
        """Closure matching the solver API: eps_fn(x, t) -> eps.  With
        ``lengths``, the closure denoises a right-padded batch with pad
        positions masked (see :meth:`eps`)."""
        return lambda x, t: self.eps(params, x, t, lengths=lengths)

    def loss(
        self, params: dict, batch: dict, rng: jax.Array, schedule: NoiseSchedule
    ) -> tuple[Array, dict]:
        """Eps-matching diffusion loss on clean latents batch["latents"]."""
        x0 = batch["latents"].astype(jnp.float32)
        kt, ke = jax.random.split(rng)
        b = x0.shape[0]
        # low-discrepancy time sampling across the batch
        u = (jax.random.uniform(kt, ()) + jnp.arange(b) / b) % 1.0
        t = schedule.t_end + (schedule.t_begin - schedule.t_end) * u
        eps = jax.random.normal(ke, x0.shape, jnp.float32)
        a = schedule.alpha(t)[:, None, None]
        s = schedule.sigma(t)[:, None, None]
        x_t = a * x0 + s * eps
        # per-sample t: vmap the scalar-t eps over the batch
        pred = jax.vmap(
            lambda xi, ti: self.eps(params, xi[None], ti)[0]
        )(x_t.astype(self.config.dtype), t)
        mse = jnp.mean((pred.astype(jnp.float32) - eps) ** 2)
        return mse, {"diffusion_mse": mse}
