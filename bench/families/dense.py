"""The dense decoder: pre-norm blocks of grouped-query attention with
rotary positions and a SwiGLU feed-forward, an output head that may be
the tied embedding.

The program's parameter layout, which the reference follows so that both
start from the same weights:

    embed [V, d], final_norm.scale [d], lm_head [d, V] (untied),
    periods.l0.{norm1,norm2}.scale [L, d],
    periods.l0.attn.{wq,wk,wv} [L, d, heads*hd], wo [L, heads*hd, d],
    periods.l0.attn.{bq,bk,bv} (with attention_bias),
    periods.l0.mlp.{w_gate,w_up} [L, d, ff], w_down [L, ff, d],
    pre_blocks [] (no leading dense layers).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import Arith, rmsnorm, rope

# program ModelConfig field <- configuration file key
KEYS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_kv_heads": "num_key_value_heads",
        "d_ff": "intermediate_size", "vocab_size": "vocab_size",
        "head_dim": "head_dim", "qkv_bias": "attention_bias",
        "tie_embeddings": "tie_word_embeddings",
        "rope_theta": "rope_theta", "param_dtype": "param_dtype",
        "compute_dtype": "compute_dtype"}
# top-level containers of the program's tree that are lists
LISTS = ("pre_blocks",)
PROGRAM_NORM_EPS = 1e-6


def require(mc):
    """What the program's dense block fixes and takes no key for."""
    if mc["rms_norm_eps"] != PROGRAM_NORM_EPS or mc["hidden_act"] != "silu":
        raise SystemExit("the program's dense block has rms_norm_eps "
                         f"{PROGRAM_NORM_EPS} and silu")


# ------------------------------------------------------------------ weights
def param_shapes(mc):
    """{path: (shape, kind)} of the program's parameter layout."""
    d, V, L = mc["hidden_size"], mc["vocab_size"], mc["num_hidden_layers"]
    H, KV, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    ff = mc["intermediate_size"]
    out = {"embed": ((V, d), "embed"), "final_norm.scale": ((d,), "norm")}
    if not mc["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "matrix")
    p = "periods.l0."
    out.update({
        p + "norm1.scale": ((L, d), "norm"),
        p + "norm2.scale": ((L, d), "norm"),
        p + "attn.wq": ((L, d, H * hd), "matrix"),
        p + "attn.wk": ((L, d, KV * hd), "matrix"),
        p + "attn.wv": ((L, d, KV * hd), "matrix"),
        p + "attn.wo": ((L, H * hd, d), "matrix"),
        p + "mlp.w_gate": ((L, d, ff), "matrix"),
        p + "mlp.w_up": ((L, d, ff), "matrix"),
        p + "mlp.w_down": ((L, ff, d), "matrix"),
    })
    if mc["attention_bias"]:
        out.update({p + "attn.bq": ((L, H * hd), "bias"),
                    p + "attn.bk": ((L, KV * hd), "bias"),
                    p + "attn.bv": ((L, KV * hd), "bias")})
    return out


# ---------------------------------------------------------------- the model
def _block(mc, ar, x, p):
    B, S, d = x.shape
    H, KV, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    eps = mc["rms_norm_eps"]
    h = rmsnorm(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q, k, v = ar.mm(h, a["wq"]), ar.mm(h, a["wk"]), ar.mm(h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(B, S, H, hd), mc["rope_theta"])
    k = rope(k.reshape(B, S, KV, hd), mc["rope_theta"])
    v = v.reshape(B, S, KV, hd)
    if KV != H:
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = ar.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(hd ** -0.5, x.dtype)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    allowed = ki <= qi
    window = (mc.get("sliding_window")
              if mc.get("use_sliding_window", True) else None)
    if window and window < S:
        allowed &= qi - ki < window
    s = jnp.where(allowed, s, jnp.asarray(-1e30, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = ar.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    x = x + ar.mm(o, a["wo"])
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    g = ar.mm(h, m["w_gate"])
    return x + ar.mm(jax.nn.silu(g) * ar.mm(h, m["w_up"]), m["w_down"])


def loss(mc, precision, params, batch):
    """Mean next-token cross-entropy over the batch's masked positions."""
    ar = Arith(precision)
    x = params["embed"][batch["tokens"]]
    body = jax.checkpoint(lambda x, p: (_block(mc, ar, x, p), None))
    x, _ = jax.lax.scan(body, x, params["periods"]["l0"])
    x = rmsnorm(x, params["final_norm"]["scale"], mc["rms_norm_eps"])
    w = (params["embed"].T if mc["tie_word_embeddings"]
         else params["lm_head"])
    logits = ar.mm(x, w).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------------- counts
def _dims(mc):
    return (mc["hidden_size"], mc["num_attention_heads"],
            mc["num_key_value_heads"], mc["head_dim"],
            mc["intermediate_size"], mc["vocab_size"],
            mc["num_hidden_layers"])


def n_matmul(mc) -> int:
    """Weights in a matrix multiplication per token: the layers'
    projections and feed-forward, and the output head (the tied embedding
    where the head is tied)."""
    d, H, KV, hd, ff, V, L = _dims(mc)
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return L * per_layer + d * V


def n_vector(mc) -> int:
    """Norm scales and biases: elementwise, no matrix multiplication."""
    d, H, KV, hd, ff, V, L = _dims(mc)
    bias = (H * hd + 2 * KV * hd) if mc["attention_bias"] else 0
    return L * (2 * d + bias) + d


def n_gather(mc) -> int:
    """The untied embedding table, read by a gather and never multiplied."""
    d, V = mc["hidden_size"], mc["vocab_size"]
    return 0 if mc["tie_word_embeddings"] else V * d


def n_params(mc) -> int:
    return n_matmul(mc) + n_vector(mc) + n_gather(mc)


def attention(mc):
    """The causal attention of the model: heads, kv heads, q/k width, v
    width and layers."""
    hd = mc["head_dim"]
    return {"heads": mc["num_attention_heads"],
            "kv_heads": mc["num_key_value_heads"], "d_qk": hd, "d_v": hd,
            "layers": mc["num_hidden_layers"]}
