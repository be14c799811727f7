"""Core layers (raw JAX): norms, RoPE, dense/SwiGLU FFN, chunked softmax-xent.

All layers are functional: ``init_*`` build param pytrees, ``*_apply`` run
them. Compute runs in ``cfg.compute_dtype``; params live in
``cfg.param_dtype``; reductions (norms, softmax, loss) run in fp32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.utils import fold_in_name


# --------------------------------------------------------------------------- init
def dense_init(key, shape, dtype, scale: float | None = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) > 1 else 1
    if scale is None:
        scale = fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------- norm
def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


# the turns over the original positions where YaRN's ramp starts and ends,
# as DeepSeek-V2 fixes them
YARN_BETA_FAST = 32
YARN_BETA_SLOW = 1


def yarn_freqs(head_dim: int, theta: float, factor: float, original_max: int):
    """YaRN's inverse frequencies as DeepSeek-V2 sets them once (a static
    blend): the plain rope frequency for the dims that turn more than
    ``YARN_BETA_FAST`` times over the original ``original_max`` positions,
    that frequency over ``factor`` for the dims that turn fewer than
    ``YARN_BETA_SLOW`` times, and a linear ramp between the two."""
    def dim_of(turns):
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(YARN_BETA_FAST)), 0)
    high = min(math.ceil(dim_of(YARN_BETA_SLOW)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x, positions, theta: float, freqs=None):
    """x: [..., S, H, hd]; positions: [..., S] int32. ``freqs`` [hd/2]
    replaces the plain frequencies of ``theta`` (YaRN's, ``yarn_freqs``)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]               # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------- ffn
def init_swiglu(key, d_model, d_ff, dtype):
    ks = {n: fold_in_name(key, n) for n in ("gate", "up", "down")}
    return {
        "w_gate": dense_init(ks["gate"], (d_model, d_ff), dtype),
        "w_up": dense_init(ks["up"], (d_model, d_ff), dtype),
        "w_down": dense_init(ks["down"], (d_ff, d_model), dtype),
    }


def swiglu_apply(p, x, cdtype):
    g = x @ p["w_gate"].astype(cdtype)
    u = x @ p["w_up"].astype(cdtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(cdtype)


def init_gelu_mlp(key, d_model, d_ff, dtype):
    ks = {n: fold_in_name(key, n) for n in ("up", "down")}
    return {
        "w_up": dense_init(ks["up"], (d_model, d_ff), dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(ks["down"], (d_ff, d_model), dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp_apply(p, x, cdtype):
    h = jax.nn.gelu(x @ p["w_up"].astype(cdtype) + p["b_up"].astype(cdtype))
    return h @ p["w_down"].astype(cdtype) + p["b_down"].astype(cdtype)


# ----------------------------------------------------------------- chunked loss
def chunked_softmax_xent(hidden, w_embed, labels, mask, chunk: int):
    """Cross-entropy without materializing [B,S,V] logits.

    hidden: [B, S, d] (compute dtype); w_embed: [V, d]; labels/mask: [B, S].
    Scans over sequence chunks; per-chunk logits are [B, chunk, V].
    Returns (sum_loss, sum_mask) as fp32 scalars.
    """
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    if S % chunk:                         # pad sequence; padded rows carry mask 0
        pad = chunk - S % chunk
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        S += pad
    n = S // chunk
    h = hidden.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)         # [n,B,c,D]
    y = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    m = mask.reshape(B, n, chunk).transpose(1, 0, 2)
    we = w_embed

    def body(carry, inp):
        s_loss, s_cnt = carry
        hc, yc, mc = inp
        logits = (hc @ we.T.astype(hc.dtype)).astype(jnp.float32)    # [B,c,V]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return (s_loss + jnp.sum(nll), s_cnt + jnp.sum(mc)), None

    (s_loss, s_cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (h, y, m))
    return s_loss, s_cnt
