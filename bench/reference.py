"""Plain float32 reference of the served sampler: the denoiser and ERA.

Written from the published descriptions and the benchmark's own
configuration files (``bench/configs``); it imports nothing of the program.
It reads the weights the benchmark drew (``bench/weights.py``), laid out
under the parameter names the program uses, which the benchmark checks leaf
by leaf against the program's own parameter shapes.

* Denoiser: ``eps(x_t, t) = head(stack(in_proj(x_t) + time_mlp(t))) +
  x_t``.  The stack runs bidirectionally, each layer by the ``reference``
  of its kind's module, ``bench/layers/<kind>.py`` (found through
  ``loader.layer``), which builds on the primitives here: ``mm``,
  ``rmsnorm``, ``linear``, ``rope``, GQA ``attention`` (with an optional
  window: keys with ``|q - k| < window``) and the SwiGLU ``mlp``.
* Sampler: ERA-Solver (Algorithm 1 of arXiv:2301.12935) with per-sample
  error-robust selection, order-4 Adams-Moulton corrector, the DDIM update
  and the linear-beta VP schedule on a uniform grid, stepped on the host in
  numpy float32, one denoiser call per NFE.

Every matrix product runs at ``highest`` precision.  ``precision="fp8"``
rounds both operands of every product to float8 (e4m3, scaled per tensor)
first: the control, computed one precision below the program's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import loader

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

# the linear-beta VP schedule (DDPM betas 1e-4 .. 2e-2 over 1000 steps,
# continuous form) and the sampler's grid
BETA_0, BETA_1, TRAIN_STEPS = 1e-4, 2e-2, 1000
T_BEGIN, T_END = 1.0, 1e-3
AM4 = np.asarray([9.0, 19.0, -5.0, 1.0], np.float32) / np.float32(24.0)


# ---------------------------------------------------------------------------
# denoiser
# ---------------------------------------------------------------------------


def _round8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def mm(a, b, precision: str, spec: str | None = None):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _round8(a), _round8(b)
    if spec is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def linear(p, x, precision):
    y = mm(x, p["w"], precision)
    return y + p["b"] if "b" in p else y


def rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x: (B, S, H, hd)."""
    hd, s = x.shape[-1], x.shape[1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, h, cfg, window, precision):
    """GQA attention with rotate-half RoPE over all positions, or with
    ``window > 0`` over keys with ``|q - k| < window``."""
    b, s, _ = h.shape
    nh, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = linear(p["wq"], h, precision).reshape(b, s, nh, hd)
    k = linear(p["wk"], h, precision).reshape(b, s, kv, hd)
    v = linear(p["wv"], h, precision).reshape(b, s, kv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    g = nh // kv
    q = q.reshape(b, s, kv, g, hd)
    scores = mm(q, k, precision, "bqkgd,bskd->bkgqs") / math.sqrt(hd)
    if window > 0:
        pos = jnp.arange(s)
        allowed = jnp.abs(pos[None, :] - pos[:, None]) < window     # (q, k)
        scores = jnp.where(allowed, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = mm(w, v, precision, "bkgqs,bskd->bqkgd").reshape(b, s, nh * hd)
    return linear(p["wo"], out, precision)


def mlp(p, h, precision):
    """SwiGLU: ``wo(silu(wg h) * wi h)``."""
    gate = jax.nn.silu(linear(p["wg"], h, precision))
    return linear(p["wo"], gate * linear(p["wi"], h, precision), precision)


def _time_embed(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(1e4) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.float32(1000.0) * t * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


def eps(params, x, t, cfg: dict, precision: str = "f32"):
    """eps_theta(x_t, t) for x (B, S, d) float32 and a scalar t."""
    tm = params["time_mlp"]
    tcond = _time_embed(t, cfg["denoiser"]["time_embed_dim"])[None]
    tcond = linear(tm["w2"], jax.nn.silu(linear(tm["w1"], tcond, precision)), precision)
    h = linear(params["in_proj"], x, precision) + tcond[:, None, :]
    segs = params["backbone"]["segs"]
    for i, (kind, _count) in enumerate(cfg["layer_types"]):
        layer = loader.layer(kind).reference
        h, _ = jax.lax.scan(lambda c, p: (layer(p, c, cfg, precision), None), h,
                            segs[f"{i}_{kind}"])
    h = rmsnorm(params["backbone"]["final_norm"]["scale"], h, cfg["rms_norm_eps"])
    return linear(params["eps_head"], h, precision) + x


@functools.lru_cache(maxsize=None)
def _jitted_eps(cfg_key: str, precision: str):
    import json

    cfg = json.loads(cfg_key)
    return jax.jit(lambda p, x, t: eps(p, x, t, cfg, precision))


# ---------------------------------------------------------------------------
# ERA-Solver on the host
# ---------------------------------------------------------------------------


def log_alpha_bar(t):
    b0, b1 = BETA_0 * TRAIN_STEPS, BETA_1 * TRAIN_STEPS
    t = np.float32(t)
    return np.float32(-0.25) * t * t * np.float32(b1 - b0) - np.float32(0.5 * b0) * t


def alpha(t):
    return np.exp(np.float32(0.5) * log_alpha_bar(t))


def sigma(t):
    return np.sqrt(-np.expm1(log_alpha_bar(t)))


def ddim_coeffs(t_cur, t_next):
    cx = alpha(t_next) / alpha(t_cur)
    return np.float32(cx), np.float32(sigma(t_next) - cx * sigma(t_cur))


def ers_select(i: int, k: int, power: float) -> list[int]:
    """Error-robust selection (Eq. 16/17): tau_m = floor((m/k)^power * i),
    then forced strictly increasing within [0, i]."""
    taus = [
        int(np.floor(np.float32(m / k) ** np.float32(power) * np.float32(i)))
        for m in range(1, k + 1)
    ]
    out, prev = [], -1
    for t in taus:
        prev = max(t, prev + 1)
        out.append(prev)
    nxt = i + 1
    for m in reversed(range(k)):
        out[m] = min(out[m], nxt - 1)
        nxt = out[m]
    return [max(t, 0) for t in out]


def lagrange_weights(t_nodes, t_eval) -> np.ndarray:
    t_nodes = np.asarray(t_nodes, np.float32)
    w = np.ones(len(t_nodes), np.float32)
    for m in range(len(t_nodes)):
        for j in range(len(t_nodes)):
            if j != m:
                w[m] *= (np.float32(t_eval) - t_nodes[j]) / (t_nodes[m] - t_nodes[j])
    return w


def sample(params, x_init: np.ndarray, cfg: dict, nfe: int, k: int = 4,
           lam: float = 5.0, precision: str = "f32") -> np.ndarray:
    """x0 of ERA-Solver (per-sample ERS) from x_T = ``x_init`` (B, S, d)."""
    import json

    fn = _jitted_eps(json.dumps(cfg, sort_keys=True), precision)
    ts = np.linspace(np.float32(T_BEGIN), np.float32(T_END), nfe + 1, dtype=np.float32)

    def observe(x, t):
        return np.asarray(fn(params, jnp.asarray(x), jnp.float32(t)), np.float32)

    rows = x_init.shape[0]
    x = np.asarray(x_init, np.float32)
    buf = [observe(x, ts[0])]
    de = np.full(rows, lam, np.float32)
    for i in range(nfe):
        t_cur, t_next = ts[i], ts[i + 1]
        cx, ce = ddim_coeffs(t_cur, t_next)
        if i < k - 1:
            eps_bar = buf[i]
            x_next = cx * x + ce * buf[i]
        else:
            eps_bar = np.empty_like(x)
            x_next = np.empty_like(x)
            for r in range(rows):
                tau = ers_select(i, k, float(de[r] / np.float32(lam)))
                w = lagrange_weights([ts[j] for j in tau], t_next)
                eb = sum(w[m] * buf[tau[m]][r] for m in range(k))
                corr = (AM4[0] * eb + AM4[1] * buf[i][r] + AM4[2] * buf[i - 1][r]
                        + AM4[3] * buf[i - 2][r])
                eps_bar[r] = eb
                x_next[r] = cx * x[r] + ce * corr
        if i + 1 < nfe:
            e_new = observe(x_next, t_next)
            if i >= k - 1:
                d = (e_new - eps_bar).reshape(rows, -1).astype(np.float64)
                de = np.sqrt(np.sum(d * d, axis=1)).astype(np.float32)
            buf.append(e_new)
        x = x_next
    return x


def request_noise(seed: int, rows: int, seq_len: int, d: int) -> np.ndarray:
    """x_T of a request as the serving contract defines it:
    ``normal(PRNGKey(seed), (rows, seq_len, d))`` in float32."""
    return np.asarray(
        jax.random.normal(jax.random.PRNGKey(seed), (rows, seq_len, d), jnp.float32)
    )
