"""Unified decoder-only LM covering dense / MoE / hybrid (jamba) / xLSTM /
VLM architectures.

Layers are grouped into repeating *periods* (dense: 1 block, jamba: 8,
xlstm: 2); period parameters are stacked on a leading axis and the stack is
traversed with ``lax.scan`` (+ optional remat) so the HLO stays one-period
sized regardless of depth — essential for compiling 40 full-size dry-run
configs on a CPU host.

API (all functional):
    init(key, cfg)                                   -> params
    forward(params, tokens, cfg, ...)                -> hidden [B,S,d]
    loss_fn(params, batch, cfg)                      -> (loss, metrics)
    prefill(params, batch, cfg)                      -> (cache, last_logits)
    decode_step(params, cache, tokens, pos, cfg)     -> (logits, cache)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import gqa_attention_block, init_gqa, init_mla, mla_attention_block
from repro.models.moe import add_counts, init_moe, moe_apply
from repro.models.ssm import init_mamba, mamba_block
from repro.models.xlstm import init_mlstm, init_slstm, mlstm_block, slstm_block
from repro.utils import fold_in_name


# ------------------------------------------------------------------ block init
def _init_block(key, cfg, kind):
    d = cfg.d_model
    p: dict[str, Any] = {"norm1": L.init_rmsnorm(d, cfg.pdtype)}
    mixer = kind["mixer"]
    if mixer == "attn":
        p["attn"] = init_mla(fold_in_name(key, "attn"), cfg) if cfg.mla \
            else init_gqa(fold_in_name(key, "attn"), cfg)
    elif mixer == "mamba":
        p["mamba"] = init_mamba(fold_in_name(key, "mamba"), cfg)
    elif mixer == "mlstm":
        p["mlstm"] = init_mlstm(fold_in_name(key, "mlstm"), cfg)
    elif mixer == "slstm":
        p["slstm"] = init_slstm(fold_in_name(key, "slstm"), cfg)
    else:
        raise ValueError(mixer)
    if kind["ffn"] == "dense":
        p["norm2"] = L.init_rmsnorm(d, cfg.pdtype)
        p["mlp"] = L.init_swiglu(fold_in_name(key, "mlp"), d, cfg.d_ff, cfg.pdtype)
    elif kind["ffn"] == "moe":
        p["norm2"] = L.init_rmsnorm(d, cfg.pdtype)
        p["moe"] = init_moe(fold_in_name(key, "moe"), cfg)
    return p


def _apply_block(p, x, cfg, kind, *, positions, mode, cache):
    """Returns (x, new_cache, stats): stats = {"aux": the block's auxiliary
    loss}, and an MoE block's counters (``moe.COUNTERS``)."""
    new_cache = None
    stats = {"aux": jnp.float32(0)}
    mixer = kind["mixer"]
    h = L.rmsnorm(p["norm1"], x)
    if mixer == "attn":
        fn = mla_attention_block if cfg.mla else gqa_attention_block
        h, new_cache = fn(p["attn"], h, cfg, positions=positions, mode=mode, cache=cache)
    elif mixer == "mamba":
        h, new_cache = mamba_block(p["mamba"], h, cfg, mode=mode, cache=cache)
    elif mixer == "mlstm":
        h, new_cache = mlstm_block(p["mlstm"], h, cfg, mode=mode, cache=cache)
    elif mixer == "slstm":
        h, new_cache = slstm_block(p["slstm"], h, cfg, mode=mode, cache=cache)
    from jax.ad_checkpoint import checkpoint_name
    h = checkpoint_name(h, "mixer_out")
    x = x + h
    if kind["ffn"] == "dense":
        x = x + L.swiglu_apply(p["mlp"], L.rmsnorm(p["norm2"], x), cfg.cdtype)
    elif kind["ffn"] == "moe":
        y, moe_aux = moe_apply(p["moe"], L.rmsnorm(p["norm2"], x), cfg)
        x = x + y
        stats = {"aux": cfg.router_aux_coef * moe_aux["lb_loss"],
                 "moe_held_rows": moe_aux["held_rows"],
                 "moe_load_ratio": moe_aux["load_ratio"]}
    return x, new_cache, stats


def _zero_stats(cfg):
    """The forward's stats before any block: the auxiliary loss, and the
    MoE counters where the model has expert layers."""
    stats = {"aux": jnp.float32(0)}
    if cfg.moe:
        stats.update(moe_held_rows=jnp.int32(0), moe_load_ratio=jnp.float32(0))
    return stats


# ------------------------------------------------------------------- model init
def init(key, cfg):
    kinds = cfg.layer_kinds()
    params: dict[str, Any] = {
        "embed": L.embed_init(fold_in_name(key, "embed"), (cfg.vocab_size, cfg.d_model),
                              cfg.pdtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(fold_in_name(key, "lm_head"),
                                         (cfg.d_model, cfg.vocab_size), cfg.pdtype)
    if cfg.vlm:
        # learned projector bias stub (ViT weights are external / frozen)
        params["img_norm"] = L.init_rmsnorm(cfg.d_model, cfg.pdtype)

    # leading dense layers outside the scan (e.g. DeepSeek-MoE layer 0)
    dense_kind = {"mixer": kinds[0]["mixer"], "ffn": "dense"}
    params["pre_blocks"] = [
        _init_block(fold_in_name(key, f"pre{i}"), cfg, dense_kind)
        for i in range(cfg.first_dense)
    ]

    def init_period(k):
        return {f"l{j}": _init_block(fold_in_name(k, f"l{j}"), cfg, kind)
                for j, kind in enumerate(kinds)}

    pkeys = jax.random.split(fold_in_name(key, "periods"), cfg.n_periods)
    params["periods"] = jax.vmap(init_period)(pkeys)
    return params


# ------------------------------------------------------------------- embeddings
def _embed_inputs(params, tokens, cfg, image_embeds=None):
    x = params["embed"][tokens].astype(cfg.cdtype)
    if cfg.vlm and image_embeds is not None:   # decode steps carry no new images
        img = L.rmsnorm(params["img_norm"], image_embeds.astype(cfg.cdtype))
        x = jnp.concatenate([img, x], axis=1)
    return x


# --------------------------------------------------------------------- forward
def forward(params, tokens, cfg, *, mode, positions=None, caches=None,
            image_embeds=None):
    """Returns (hidden [B,S',d], new_caches, stats): stats["aux"] is the
    auxiliary loss; an MoE model's also holds ``moe_held_rows`` and
    ``moe_load_ratio`` over its expert layers (``moe.add_counts``)."""
    kinds = cfg.layer_kinds()
    x = _embed_inputs(params, tokens, cfg, image_embeds)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)

    stats = _zero_stats(cfg)
    pre_caches = []
    for i, bp in enumerate(params["pre_blocks"]):
        c_in = caches["pre"][i] if caches is not None else None
        x, c, block = _apply_block(bp, x, cfg, {"mixer": kinds[0]["mixer"], "ffn": "dense"},
                                   positions=positions, mode=mode, cache=c_in)
        pre_caches.append(c)
        stats = add_counts(stats, block)

    def period_fn(carry, scanned):
        xc, acc = carry
        p_period, cache_period = scanned
        new_caches = {}
        for j, kind in enumerate(kinds):
            c_in = cache_period[f"l{j}"] if cache_period is not None else None
            xc, c, block = _apply_block(p_period[f"l{j}"], xc, cfg, kind,
                                        positions=positions, mode=mode, cache=c_in)
            new_caches[f"l{j}"] = c
            acc = add_counts(acc, block)
        return (xc, acc), new_caches

    if cfg.remat and mode == "train":
        if cfg.remat_policy == "save_mixer":
            # keep the expensive mixer (attention / SSM scan) outputs; only
            # recompute the cheap norm/FFN elementwise chains in backward
            policy = jax.checkpoint_policies.save_only_these_names("mixer_out")
            period_fn = jax.checkpoint(period_fn, policy=policy)
        else:
            period_fn = jax.checkpoint(period_fn)

    scan_caches = caches["periods"] if caches is not None else None
    if scan_caches is None:
        # substitute a None-free placeholder: scan needs matching pytrees
        (x, stats), out_caches = jax.lax.scan(
            lambda c, pp: period_fn(c, (pp, _none_cache_like(kinds))),
            (x, stats), params["periods"])
    else:
        (x, stats), out_caches = jax.lax.scan(
            period_fn, (x, stats), (params["periods"], scan_caches))

    x = L.rmsnorm(params["final_norm"], x)
    new_caches = {"pre": pre_caches, "periods": out_caches} \
        if (mode != "train") else None
    return x, new_caches, stats


def _none_cache_like(kinds):
    return {f"l{j}": None for j in range(len(kinds))}


# --------------------------------------------------------------------- heads
def _unembed_last(params, hidden, cfg):
    """Logits for the final position only: [B,d] @ [d,V]."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (hidden[:, -1].astype(jnp.float32) @ w.astype(jnp.float32))


# ----------------------------------------------------------------------- train
def loss_fn(params, batch, cfg):
    """batch: tokens/labels/mask [B,S(text)] (+ image_embeds for VLM).

    Returns (scalar loss, metrics dict). Image positions carry no loss. An
    MoE model's metrics also hold its counters over the forward:
    ``moe_held_rows`` and ``moe_load_ratio`` (``moe.COUNTERS``).
    """
    tokens = batch["tokens"]
    image_embeds = batch.get("image_embeds")
    hidden, _, stats = forward(params, tokens, cfg, mode="train",
                               image_embeds=image_embeds)
    aux = stats.pop("aux")
    if cfg.vlm:
        n_img = image_embeds.shape[1]
        hidden = hidden[:, n_img:]
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    s_loss, s_cnt = L.chunked_softmax_xent(hidden, w, batch["labels"], batch["mask"],
                                           cfg.loss_chunk)
    task_loss = s_loss / jnp.maximum(s_cnt, 1)
    loss = task_loss + aux
    metrics = {"task_loss": task_loss, "aux_loss": aux, "tokens": s_cnt}
    if cfg.moe:
        metrics.update(stats)
    return loss, metrics


# --------------------------------------------------------------------- serving
def make_cache(cfg, batch_size, cache_len):
    """Zero-initialized decode cache for every layer (stacked per period)."""
    kinds = cfg.layer_kinds()
    W = min(cfg.sliding_window, cache_len) if cfg.sliding_window else cache_len
    B = batch_size
    H = cfg.num_heads
    cd = cfg.cdtype

    def one(kind):
        m = kind["mixer"]
        if m == "attn":
            if cfg.mla:
                return {"c_kv": jnp.zeros((B, W, cfg.kv_lora_rank), cd),
                        "k_rope": jnp.zeros((B, W, cfg.qk_rope_head_dim), cd),
                        "len": jnp.zeros((), jnp.int32)}
            return {"k": jnp.zeros((B, W, cfg.num_kv_heads, cfg.head_dim), cd),
                    "v": jnp.zeros((B, W, cfg.num_kv_heads, cfg.head_dim), cd),
                    "len": jnp.zeros((), jnp.int32)}
        if m == "mamba":
            return {"conv": jnp.zeros((B, cfg.ssm_conv_dim - 1, cfg.d_inner), cd),
                    "h": jnp.zeros((B, cfg.d_inner, cfg.ssm_state_dim), jnp.float32)}
        if m == "mlstm":
            di = int(cfg.mlstm_proj_factor * cfg.d_model)
            hd = di // H
            return {"conv": jnp.zeros((B, cfg.ssm_conv_dim - 1, di), cd),
                    "C": jnp.zeros((B, H, hd, hd), jnp.float32),
                    "n": jnp.zeros((B, H, hd), jnp.float32),
                    "m": jnp.full((B, H), -1e30, jnp.float32)}
        if m == "slstm":
            d = cfg.d_model
            return {"conv": jnp.zeros((B, cfg.ssm_conv_dim - 1, d), cd),
                    "h": jnp.zeros((B, d), jnp.float32),
                    "c": jnp.zeros((B, d), jnp.float32),
                    "n": jnp.zeros((B, d), jnp.float32),
                    "m": jnp.full((B, d), -1e30, jnp.float32)}
        raise ValueError(m)

    period = {f"l{j}": one(kind) for j, kind in enumerate(kinds)}
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), period)
    kinds0 = {"mixer": kinds[0]["mixer"], "ffn": "dense"}
    pre = [one(kinds0) for _ in range(cfg.first_dense)]
    return {"pre": pre, "periods": stacked}


def prefill(params, batch, cfg):
    tokens = batch["tokens"]
    hidden, caches, _ = forward(params, tokens, cfg, mode="prefill",
                                image_embeds=batch.get("image_embeds"))
    return caches, _unembed_last(params, hidden, cfg)


def decode_step(params, caches, tokens, pos, cfg):
    """tokens: [B,1]; pos: scalar absolute position. -> (logits [B,V], caches)."""
    positions = jnp.asarray(pos).reshape(1)
    hidden, new_caches, _ = forward(params, tokens, cfg, mode="decode",
                                    positions=positions, caches=caches)
    return _unembed_last(params, hidden, cfg), new_caches
