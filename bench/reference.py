"""Plain float32 reference of the served sampler: the denoiser and ERA.

Written from the published descriptions and the benchmark's own
configuration files (``bench/configs``); it imports nothing of the program.
It reads the weights the benchmark drew (``bench/weights.py``), laid out
under the parameter names the program uses, which the benchmark checks leaf
by leaf against the program's own parameter shapes.

* Denoiser: ``eps(x_t, t) = head(stack(in_proj(x_t) + time_mlp(t))) +
  x_t``.  The stack runs bidirectionally.  A dense layer (Qwen2) is
  pre-norm GQA attention with q/k/v biases and rotate-half RoPE, then a
  SwiGLU MLP.  A Hymba layer runs attention heads and Mamba heads side by
  side on the same normed input and averages their separately normed
  outputs, then the MLP.  A window layer of the published (causal) model
  sees the ``window`` positions up to its own; run bidirectionally it sees
  them on both sides, keys with ``|q - k| < window``.
  The Mamba heads are a selective scan written as a plain ``lax.scan``
  over positions.
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


def _mm(a, b, precision: str, spec: str | None = None):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _round8(a), _round8(b)
    if spec is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _linear(p, x, precision):
    y = _mm(x, p["w"], precision)
    return y + p["b"] if "b" in p else y


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x: (B, S, H, hd)."""
    hd, s = x.shape[-1], x.shape[1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, h, cfg, window, precision):
    b, s, _ = h.shape
    nh, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _linear(p["wq"], h, precision).reshape(b, s, nh, hd)
    k = _linear(p["wk"], h, precision).reshape(b, s, kv, hd)
    v = _linear(p["wv"], h, precision).reshape(b, s, kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    g = nh // kv
    q = q.reshape(b, s, kv, g, hd)
    scores = _mm(q, k, precision, "bqkgd,bskd->bkgqs") / math.sqrt(hd)
    if window > 0:
        pos = jnp.arange(s)
        allowed = jnp.abs(pos[None, :] - pos[:, None]) < window     # (q, k)
        scores = jnp.where(allowed, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = _mm(w, v, precision, "bkgqs,bskd->bqkgd").reshape(b, s, nh * hd)
    return _linear(p["wo"], out, precision)


def _mlp(p, h, precision):
    gate = jax.nn.silu(_linear(p["wg"], h, precision))
    return _linear(p["wo"], gate * _linear(p["wi"], h, precision), precision)


def _mamba(p, h, cfg, precision):
    """Selective SSM: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t, y_t = C_t h_t."""
    n = cfg["mamba_d_state"]
    dtr = cfg["mamba_dt_rank"]
    x, z = jnp.split(_linear(p["in_proj"], h, precision), 2, axis=-1)
    width = p["conv"]["w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    x = sum(xp[:, i : i + s] * p["conv"]["w"][i] for i in range(width))
    x = jax.nn.silu(x + p["conv"]["b"])
    proj = _linear(p["x_proj"], x, precision)
    dt, bmat, cmat = jnp.split(proj, [dtr, dtr + n], axis=-1)
    dt = jax.nn.softplus(_linear(p["dt_proj"], dt, precision))      # (B,S,di)
    a = -jnp.exp(p["A_log"])                                         # (di,N)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp                   # (B,di) (B,di) (B,N) (B,N)
        state = jnp.exp(dt_t[..., None] * a) * state + (
            (dt_t * x_t)[..., None] * b_t[:, None, :]
        )
        return state, jnp.einsum("bdn,bn->bd", state, c_t, precision=HIGHEST)

    state0 = jnp.zeros((x.shape[0], x.shape[2], n), jnp.float32)
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, x, bmat, cmat))
    _, ys = jax.lax.scan(step, state0, seq, unroll=8)
    y = jnp.moveaxis(ys, 0, 1) + x * p["D"]
    return _linear(p["out_proj"], y * jax.nn.silu(z), precision)


def _dense_layer(p, x, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p["attn"], _rmsnorm(p["ln1"]["scale"], x, eps), cfg, 0, precision)
    return x + _mlp(p["mlp"], _rmsnorm(p["ln2"]["scale"], x, eps), precision)


def _hymba_layer(p, x, cfg, window, precision):
    eps = cfg["rms_norm_eps"]
    h = _rmsnorm(p["ln1"]["scale"], x, eps)
    attn = _attention(p["attn"], h, cfg, window, precision)
    ssm = _mamba(p["mamba"], h, cfg, precision)
    x = x + 0.5 * (
        _rmsnorm(p["attn_norm"]["scale"], attn, eps)
        + _rmsnorm(p["mamba_norm"]["scale"], ssm, eps)
    )
    return x + _mlp(p["mlp"], _rmsnorm(p["ln2"]["scale"], x, eps), precision)


def _layer_fn(kind: str, cfg: dict, precision: str):
    if kind == "dense":
        return lambda p, x: _dense_layer(p, x, cfg, precision)
    if kind == "hymba_full":
        return lambda p, x: _hymba_layer(p, x, cfg, 0, precision)
    if kind == "hymba_swa":
        window = cfg["attn_window_size"]
        return lambda p, x: _hymba_layer(p, x, cfg, window, precision)
    raise ValueError(f"no reference for layer kind {kind!r}")


def _time_embed(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(1e4) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.float32(1000.0) * t * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


def eps(params, x, t, cfg: dict, precision: str = "f32"):
    """eps_theta(x_t, t) for x (B, S, d) float32 and a scalar t."""
    tm = params["time_mlp"]
    tcond = _time_embed(t, cfg["denoiser"]["time_embed_dim"])[None]
    tcond = _linear(tm["w2"], jax.nn.silu(_linear(tm["w1"], tcond, precision)), precision)
    h = _linear(params["in_proj"], x, precision) + tcond[:, None, :]
    segs = params["backbone"]["segs"]
    for i, (kind, _count) in enumerate(cfg["layer_types"]):
        layer = _layer_fn(kind, cfg, precision)
        h, _ = jax.lax.scan(lambda c, p: (layer(p, c), None), h, segs[f"{i}_{kind}"])
    h = _rmsnorm(params["backbone"]["final_norm"]["scale"], h, cfg["rms_norm_eps"])
    return _linear(params["eps_head"], h, precision) + x


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
