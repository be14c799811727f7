"""Attention: GQA (flash-style chunked softmax), MLA (latent KV), decode paths.

The train/prefill path is an online-softmax blockwise attention written with
``lax.scan`` so that no [S, S] score matrix is ever materialized — this is
the jnp twin of the Pallas ``flash_attention`` kernel (kernels/ops.py runs
the Pallas version on TPU).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, dense_init, init_rmsnorm,
                                 rmsnorm, yarn_freqs, yarn_mscale)
from repro.utils import fold_in_name

NEG_INF = -1e30


# =============================================================== GQA attention
def init_gqa(key, cfg):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = {n: fold_in_name(key, n) for n in ("wq", "wk", "wv", "wo")}
    p = {
        "wq": dense_init(ks["wq"], (d, H * hd), cfg.pdtype),
        "wk": dense_init(ks["wk"], (d, KV * hd), cfg.pdtype),
        "wv": dense_init(ks["wv"], (d, KV * hd), cfg.pdtype),
        "wo": dense_init(ks["wo"], (H * hd, d), cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((KV * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((KV * hd,), cfg.pdtype)
    return p


def gqa_project(p, x, cfg):
    """x: [B,S,d] -> q [B,S,H,hd], k,v [B,S,KV,hd] (un-roped)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cd = cfg.cdtype
    q = x @ p["wq"].astype(cd)
    k = x @ p["wk"].astype(cd)
    v = x @ p["wv"].astype(cd)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(cd), k + p["bk"].astype(cd), v + p["bv"].astype(cd)
    return (q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd), v.reshape(B, S, KV, hd))


def gqa_attention_block(p, x, cfg, *, positions, mode, cache=None, dispatch=None):
    """Full GQA block. mode: 'train'|'prefill'|'decode'.

    cache (prefill out / decode in-out): dict(k, v: [B,W,KV,hd], len: scalar).
    positions: [B?, S] absolute positions (we use a shared [S] vector).
    Returns (out [B,S,d], new_cache).
    """
    from repro.kernels import ops as kops
    B, S, _ = x.shape
    cd = cfg.cdtype
    q, k, v = gqa_project(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    window = cfg.sliding_window
    mm_dtype = jnp.bfloat16 if cfg.attn_bf16 else None

    if mode in ("train", "prefill"):
        if cfg.seq_shard_attn:
            # sequence-parallel attention: when heads % model_axis != 0 GSPMD
            # would otherwise shard the hd CONTRACTION and all-reduce scores
            # per kv block. Instead: queries sharded over S on "model", k/v
            # gathered once per layer, attention fully local per device.
            from jax.sharding import PartitionSpec as P
            dp = cfg.dp_axes if len(cfg.dp_axes) > 1 else cfg.dp_axes[0]
            q = jax.lax.with_sharding_constraint(q, P(dp, "model", None, None))
            k = jax.lax.with_sharding_constraint(k, P(dp, None, None, None))
            v = jax.lax.with_sharding_constraint(v, P(dp, None, None, None))
        out = kops.flash_attention(q, k, v, causal=cfg.causal, window=window,
                                   block_kv=cfg.attn_block_kv,
                                   mm_dtype=mm_dtype)
        if cfg.seq_shard_attn:
            from jax.sharding import PartitionSpec as P
            dp = cfg.dp_axes if len(cfg.dp_axes) > 1 else cfg.dp_axes[0]
            out = jax.lax.with_sharding_constraint(out, P(dp, "model", None, None))
        new_cache = None
        if mode == "prefill":
            W = min(window, S) if window else S
            kc, vc = k[:, S - W:], v[:, S - W:]
            if window and S > window:
                # ring layout: absolute position p lives at slot p % W
                kc = jnp.roll(kc, S % W, axis=1)
                vc = jnp.roll(vc, S % W, axis=1)
            new_cache = {"k": kc, "v": vc,
                         "len": jnp.asarray(min(W, S), jnp.int32)}
    else:  # decode: S == 1
        W = cache["k"].shape[1]
        pos = positions[-1]                                             # scalar
        slot = (pos % W if window else pos).astype(jnp.int32)
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        kv_len = jnp.minimum(pos + 1, W).astype(jnp.int32)
        out = kops.decode_attention(q, k_cache, v_cache, kv_len=kv_len)
        new_cache = {"k": k_cache, "v": v_cache, "len": kv_len}

    B_, S_, H, hd = out.shape
    y = out.reshape(B_, S_, H * hd) @ p["wo"].astype(cd)
    return y, new_cache


# ============================================================== MLA attention
def init_mla(key, cfg):
    """The query goes through a low-rank path (``wq_a``, ``q_norm``,
    ``wq_b``; MiniCPM3) where ``q_lora_rank`` > 0, else through one plain
    ``wq`` (DeepSeek-V2-Lite)."""
    d = cfg.d_model
    H = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = {n: fold_in_name(key, n)
          for n in ("wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")}
    if qr:
        q = {"wq_a": dense_init(ks["wq_a"], (d, qr), cfg.pdtype),
             "q_norm": init_rmsnorm(qr, cfg.pdtype),
             "wq_b": dense_init(ks["wq_b"], (qr, H * (nope + rope)),
                                cfg.pdtype)}
    else:
        q = {"wq": dense_init(ks["wq"], (d, H * (nope + rope)), cfg.pdtype)}
    return {
        **q,
        "wkv_a": dense_init(ks["wkv_a"], (d, kvr + rope), cfg.pdtype),
        "kv_norm": init_rmsnorm(kvr, cfg.pdtype),
        "wkv_b": dense_init(ks["wkv_b"], (kvr, H * (nope + vd)), cfg.pdtype),
        "wo": dense_init(ks["wo"], (H * vd, d), cfg.pdtype),
    }


def mla_rope_and_scale(cfg):
    """(rope frequencies [rope/2] or None for the plain ones, softmax
    scale) of the latent attention: YaRN's blend and (qk width)^-0.5 times
    its temperature mscale(factor, mscale_all_dim) squared where the
    configuration scales rope (DeepSeek-V2), else the plain rope and
    (qk width)^-0.5."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if not cfg.yarn_factor:
        return None, scale
    freqs = yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_max_position)
    if cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return freqs, scale


def mla_attention_block(p, x, cfg, *, positions, mode, cache=None, dispatch=None):
    """MLA (Multi-head Latent Attention, MiniCPM3/DeepSeek-V2 style).

    Prefill: expand latents to full k/v, run flash attention.
    Decode: 'absorbed' path — scores and context computed directly in the
    latent space; the KV cache stores only [B,W,kvr] latents + [B,W,rope]
    shared roped keys (the MLA memory win).
    """
    from repro.kernels import ops as kops
    B, S, d = x.shape
    cd = cfg.cdtype
    H = cfg.num_heads
    nope, rope, vd, kvr = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim, cfg.kv_lora_rank)
    freqs, scale = mla_rope_and_scale(cfg)

    if "wq" in p:
        q = x @ p["wq"].astype(cd)
    else:
        q = rmsnorm(p["q_norm"], x @ p["wq_a"].astype(cd)) @ p["wq_b"].astype(cd)
    q = q.reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, freqs)

    kv_a = x @ p["wkv_a"].astype(cd)                                    # [B,S,kvr+rope]
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., :kvr])                       # latent
    k_rope = apply_rope(kv_a[..., kvr:].reshape(B, S, 1, rope), positions,
                        cfg.rope_theta, freqs)

    wkv_b = p["wkv_b"].astype(cd).reshape(kvr, H, nope + vd)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]                   # [kvr,H,nope],[kvr,H,vd]

    if mode in ("train", "prefill"):
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_uk)
        v = jnp.einsum("bsr,rhv->bshv", c_kv, w_uv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], axis=-1)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        # pad v to head_dim of k for the shared flash kernel, then slice back
        pad = (nope + rope) - vd
        v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad))) if pad > 0 else v
        out = kops.flash_attention(qfull, k, v_p, causal=cfg.causal,
                                   window=cfg.sliding_window,
                                   block_kv=cfg.attn_block_kv, scale=scale)
        out = out[..., :vd]
        new_cache = None
        if mode == "prefill":
            W = min(cfg.sliding_window, S) if cfg.sliding_window else S
            cc, rc = c_kv[:, S - W:], k_rope[:, S - W:, 0]
            if cfg.sliding_window and S > cfg.sliding_window:
                cc = jnp.roll(cc, S % W, axis=1)
                rc = jnp.roll(rc, S % W, axis=1)
            new_cache = {"c_kv": cc, "k_rope": rc,
                         "len": jnp.asarray(min(W, S), jnp.int32)}
    else:  # decode (absorbed)
        W = cache["c_kv"].shape[1]
        pos = positions[-1]
        slot = (pos % W if cfg.sliding_window else pos).astype(jnp.int32)
        c_cache = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv, (0, slot, 0))
        r_cache = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope[:, :, 0], (0, slot, 0))
        kv_len = jnp.minimum(pos + 1, W).astype(jnp.int32)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))                    # [B,1,H,kvr]
        s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_cache.astype(jnp.float32))
             + jnp.einsum("bqhp,bsp->bhqs", q_rope.astype(jnp.float32),
                          r_cache.astype(jnp.float32))) * scale
        valid = jnp.arange(W)[None, :] < kv_len
        s = jnp.where(valid[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhqs,bsr->bqhr", w, c_cache.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32)).astype(cd)
        new_cache = {"c_kv": c_cache, "k_rope": r_cache, "len": kv_len}

    y = out.reshape(B, S, H * vd) @ p["wo"].astype(cd)
    return y, new_cache


# ===================================================== cross-attention (enc-dec)
def init_cross_attn(key, cfg):
    d, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    ks = {n: fold_in_name(key, n) for n in ("wq", "wk", "wv", "wo")}
    return {
        "wq": dense_init(ks["wq"], (d, H * hd), cfg.pdtype),
        "wk": dense_init(ks["wk"], (d, H * hd), cfg.pdtype),
        "wv": dense_init(ks["wv"], (d, H * hd), cfg.pdtype),
        "wo": dense_init(ks["wo"], (H * hd, d), cfg.pdtype),
    }


def cross_attention(p, x, enc, cfg):
    """x: [B,S,d] queries; enc: [B,T,d] encoder states (full, non-causal)."""
    B, S, _ = x.shape
    T = enc.shape[1]
    H, hd = cfg.num_heads, cfg.head_dim
    cd = cfg.cdtype
    q = (x @ p["wq"].astype(cd)).reshape(B, S, H, hd)
    k = (enc @ p["wk"].astype(cd)).reshape(B, T, H, hd)
    v = (enc @ p["wv"].astype(cd)).reshape(B, T, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * hd ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(cd)
    return out.reshape(B, S, H * hd) @ p["wo"].astype(cd)
