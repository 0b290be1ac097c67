"""Plain reference for the served model: DiT with adaLN-Zero (Peebles &
Xie 2023) and the respaced ancestral DDPM sampler with classifier-free
guidance, in float32 ``jax.numpy``.

It imports nothing of the program under test. It reads the weights tree
that ``harness.weights`` builds (the layout the program's DiT takes) and
computes, for each request, the sample the served path should return:

- DiT forward: patch embedding (patch vectors flattened row, column,
  channel) plus the fixed position table; sinusoidal timestep embedding
  (cos half first, max period 10^4) through Linear-SiLU-Linear, plus the
  class table (row ``n_classes`` is the null class); per block
  ``mod = Linear(SiLU(c))`` split into shift/scale/gate for attention
  and MLP, LayerNorm without affine (eps 1e-6), 16-head softmax
  attention, tanh-GELU MLP; adaLN final layer. The output is eps only
  (4 channels): the served model has no learned variance.
- Sampler: linear betas 1e-4..0.02 over T = 1000, respaced to ``steps``
  evenly spaced timesteps (Nichol & Dhariwal); per step the guided eps
  ``eps_u + s (eps_c - eps_u)``, the x0 prediction, the posterior mean
  and, except at the last step, posterior-variance noise. The request's
  noise is ``normal(fold_in(PRNGKey(seed), n))`` for x_T (``n`` = chain
  length) and ``normal(fold_in(PRNGKey(seed), i))`` at scan position
  ``i``: that convention is part of what a request's seed means.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def schedule(steps: int, T: int = 1000, beta_start: float = 1e-4,
             beta_end: float = 0.02):
    """Respaced linear schedule: descending original timesteps and the
    ascending respaced arrays, computed in float64, stored as float32."""
    betas = np.linspace(beta_start, beta_end, T, dtype=np.float64)
    abar_full = np.cumprod(1.0 - betas)
    use_ts = np.unique(np.linspace(0, T - 1, steps).round().astype(np.int64))
    use_ts = use_ts[::-1].copy()
    abar = abar_full[use_ts[::-1]]
    abar_prev = np.concatenate([[1.0], abar[:-1]])
    alphas = abar / abar_prev
    betas_r = 1.0 - alphas
    post_var = betas_r * (1.0 - abar_prev) / (1.0 - abar)
    f = lambda a: np.asarray(a, np.float32)
    return {"use_ts": use_ts.astype(np.int32), "abar": f(abar),
            "abar_prev": f(abar_prev), "alphas": f(alphas),
            "betas": f(betas_r), "post_var": f(post_var)}


def _layernorm(x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _modulate(x, shift, scale):
    return _layernorm(x) * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _timestep_embedding(t, dim=256, max_period=10000.0):
    half = dim // 2
    freqs = np.exp(-np.log(max_period) * np.arange(half) / half)
    ang = t.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def fake_quant(x, bits: int, axis):
    """Symmetric round-to-nearest at ``bits``, one absmax scale over
    ``axis`` (an axis or a tuple of axes)."""
    q = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / q
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -q, q) * s


def forward(w, model: dict, x, t, y, bits=None):
    """eps prediction. ``w``: weights tree in float32; ``model``: the
    configuration (published DiT constructor keys); x (B, H, W, C);
    t, y (B,) int. With ``bits``, every linear runs on fake-quantized
    operands at the configuration's granularity (weights per output
    channel, activations per tensor of each sample); attention stays in
    float."""
    B, Hs, Ws, C = x.shape
    p, d, nh = model["patch_size"], model["hidden_size"], model["num_heads"]
    hd = d // nh
    g = Hs // p

    def lin(a, p):
        wt = p["w"]
        if bits:
            a = fake_quant(a, bits, tuple(range(1, a.ndim)))
            wt = fake_quant(wt, bits, -2)
        return a @ wt + p["b"]

    tok = x.reshape(B, g, p, g, p, C).transpose(0, 1, 3, 2, 4, 5)
    tok = tok.reshape(B, g * g, p * p * C)
    h = lin(tok, w["x_proj"]) + w["pos"][None]
    temb = jax.nn.silu(lin(_timestep_embedding(t), w["t_mlp1"]))
    temb = lin(temb, w["t_mlp2"])
    c = temb + w["y_embed"]["emb"][y]
    sc = jax.nn.silu(c)

    def block(h, bw):
        mod = lin(sc, bw["ada"])
        sh1, s1, g1, sh2, s2, g2 = jnp.split(mod, 6, axis=-1)
        qkv = lin(_modulate(h, sh1, s1), bw["qkv"])
        q, k, v = jnp.split(qkv.reshape(B, -1, 3, nh, hd), 3, axis=2)
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + g1[:, None, :] * lin(o.reshape(B, -1, d), bw["proj"])
        m = jax.nn.gelu(lin(_modulate(h, sh2, s2), bw["fc1"]),
                        approximate=True)
        return h + g2[:, None, :] * lin(m, bw["fc2"]), None

    h, _ = jax.lax.scan(block, h, w["blocks"])
    sh, s = jnp.split(lin(sc, w["final_ada"]), 2, axis=-1)
    out = lin(_modulate(h, sh, s), w["final"])
    out = out.reshape(B, g, g, p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(B, Hs, Ws, C)


def sampler(model: dict, steps: int, precision: str = "highest",
            bits=None, clip_x0=None):
    """A jitted ``f(w, labels, seeds, guidance) -> samples`` that runs the
    whole chain for a block of requests at the given matmul precision;
    ``bits`` fake-quantizes every linear (the lower-precision control)."""
    S = schedule(steps)
    n = len(S["use_ts"])
    size, ch = model["input_size"], model["in_channels"]
    null = model["num_classes"]
    sshape = (size, size, ch)
    arr = {k: jnp.asarray(v) for k, v in S.items()}

    def noise(seeds, i):
        return jax.vmap(lambda sd: jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(sd), i), sshape,
            jnp.float32))(seeds)

    def run(w, labels, seeds, guidance):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        B = labels.shape[0]
        yy = jnp.concatenate([labels, jnp.full((B,), null, jnp.int32)])
        gs = guidance.reshape(B, 1, 1, 1)

        def step(x, i):
            idx = n - 1 - i
            t = jnp.full((2 * B,), arr["use_ts"][i], jnp.int32)
            eps_c, eps_u = jnp.split(
                forward(w, model, jnp.concatenate([x, x]), t, yy, bits), 2)
            eps = eps_u + gs * (eps_c - eps_u)
            abar, abar_prev = arr["abar"][idx], arr["abar_prev"][idx]
            x0 = (x - jnp.sqrt(1 - abar) * eps) / jnp.sqrt(abar)
            if clip_x0 is not None:
                x0 = jnp.clip(x0, -clip_x0, clip_x0)
            mean = (jnp.sqrt(abar_prev) * arr["betas"][idx] / (1 - abar) * x0
                    + jnp.sqrt(arr["alphas"][idx]) * (1 - abar_prev)
                    / (1 - abar) * x)
            z = noise(seeds, i)
            x = mean + (idx > 0) * jnp.sqrt(arr["post_var"][idx]) * z
            return x, None

        x = noise(seeds, n)
        x, _ = jax.lax.scan(step, x, jnp.arange(n))
        return x

    jitted = jax.jit(run)

    def call(w, labels, seeds, guidance):
        with jax.default_matmul_precision(precision):
            return jitted(w, jnp.asarray(labels, jnp.int32),
                          jnp.asarray(seeds, jnp.uint32),
                          jnp.asarray(guidance, jnp.float32))
    return call
