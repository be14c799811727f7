"""Mamba (S6) selective-state-space block.

Train/prefill use the chunked parallel scan (kernels/ops.ssm_scan — Pallas
on TPU, associative-scan jnp fallback elsewhere); decode is a single
recurrent step against a (conv tail, ssm state) cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.layers import dense_init
from repro.utils import fold_in_name


def init_mamba(key, cfg):
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state_dim
    K, dtr = cfg.ssm_conv_dim, cfg.ssm_dt_rank
    ks = {n: fold_in_name(key, n) for n in
          ("in", "conv", "xproj", "dtproj", "out")}
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))
    return {
        "w_in": dense_init(ks["in"], (d, 2 * di), cfg.pdtype),
        "conv_w": dense_init(ks["conv"], (K, di), cfg.pdtype, scale=K ** -0.5),
        "conv_b": jnp.zeros((di,), cfg.pdtype),
        "w_xproj": dense_init(ks["xproj"], (di, dtr + 2 * N), cfg.pdtype),
        "w_dtproj": dense_init(ks["dtproj"], (dtr, di), cfg.pdtype, scale=dtr ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((di,), 0.01))).astype(cfg.pdtype),
        "A_log": jnp.log(A).astype(jnp.float32),                       # keep fp32
        "D": jnp.ones((di,), jnp.float32),
        "w_out": dense_init(ks["out"], (di, d), cfg.pdtype),
    }


def _causal_conv(xi, w, b, K):
    """Depthwise causal conv. xi: [B,S,di]; w: [K,di]."""
    pad = jnp.pad(xi, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(pad[:, j:j + xi.shape[1], :] * w[j][None, None] for j in range(K))
    return y + b[None, None]


def _ssm_inputs(p, xi, cfg):
    """xi: [B,S,di] (post conv+silu) -> (dt, Bm, Cm) fp32."""
    N, dtr = cfg.ssm_state_dim, cfg.ssm_dt_rank
    proj = xi @ p["w_xproj"].astype(xi.dtype)                          # [B,S,dtr+2N]
    dt_r, Bm, Cm = jnp.split(proj, [dtr, dtr + N], axis=-1)
    dt = jax.nn.softplus(dt_r.astype(jnp.float32) @ p["w_dtproj"].astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))           # [B,S,di]
    return dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32)


def mamba_block(p, x, cfg, *, mode, cache=None):
    """x: [B,S,d]. cache (decode): {'conv': [B,K-1,di], 'h': [B,di,N]}."""
    B, S, d = x.shape
    di, N, K = cfg.d_inner, cfg.ssm_state_dim, cfg.ssm_conv_dim
    cd = cfg.cdtype
    u = x @ p["w_in"].astype(cd)                                       # [B,S,2di]
    xi, z = jnp.split(u, 2, axis=-1)

    if mode in ("train", "prefill"):
        xc = jax.nn.silu(_causal_conv(xi, p["conv_w"].astype(cd), p["conv_b"].astype(cd), K))
        dt, Bm, Cm = _ssm_inputs(p, xc, cfg)
        A = -jnp.exp(p["A_log"])
        y = kops.ssm_scan(xc, dt, A, Bm, Cm, p["D"], chunk=cfg.ssm_chunk)
        new_cache = None
        if mode == "prefill":
            # replay the tail to produce the decode cache state
            h = _final_state(xc, dt, A, Bm)
            new_cache = {"conv": xi[:, S - (K - 1):].astype(cd), "h": h}
    else:  # decode, S == 1
        conv_tail = cache["conv"]                                      # [B,K-1,di]
        window = jnp.concatenate([conv_tail, xi], axis=1)              # [B,K,di]
        xc = jnp.einsum("bkd,kd->bd", window.astype(cd), p["conv_w"].astype(cd))
        xc = jax.nn.silu(xc + p["conv_b"].astype(cd))[:, None]         # [B,1,di]
        dt, Bm, Cm = _ssm_inputs(p, xc, cfg)
        A = -jnp.exp(p["A_log"])
        h, y1 = kops.ssm_step(cache["h"], xc[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
        y = (y1 + xc[:, 0].astype(jnp.float32) * p["D"][None]).astype(cd)[:, None]
        new_cache = {"conv": window[:, 1:], "h": h}

    y = y.astype(cd) * jax.nn.silu(z)
    return y @ p["w_out"].astype(cd), new_cache


def _final_state(xc, dt, A, Bm):
    """Sequential pass for the final SSM state (prefill->decode handoff)."""
    def step(h, inp):
        xt, dtt, Bt = inp
        dA = jnp.exp(dtt[..., None] * A[None])
        h = dA * h + (dtt * xt.astype(jnp.float32))[..., None] * Bt[:, None, :]
        return h, None
    B, S, di = xc.shape
    h0 = jnp.zeros((B, di, A.shape[1]), jnp.float32)
    xs = (xc.astype(jnp.float32).transpose(1, 0, 2), dt.transpose(1, 0, 2),
          Bm.transpose(1, 0, 2))
    h, _ = jax.lax.scan(step, h0, xs)
    return h
