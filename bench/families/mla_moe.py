"""Latent attention and a mixture of experts (DeepSeek-V2): pre-norm
blocks of multi-head latent attention, with a plain query projection and
YaRN rotary positions on the rope dims, over leading dense SwiGLU layers
and then expert layers: a softmax router over every expert, greedy top-k
with the weights as they are or renormalised, the held experts' SwiGLUs
and shared experts on every token. The head is untied.

A layer holds ``n_routed_experts`` experts, experts 0..n-1 of the
``router_experts`` that its router scores (one chip's share under expert
parallelism); a slot routed to an expert that is not held adds nothing.

The program's parameter layout, which the reference follows so that both
start from the same weights (per layer; the MoE layers stacked on a
leading [L] axis under ``periods.l0``, layer 0 in ``pre_blocks.0``):

    embed [V, d], final_norm.scale [d], lm_head [d, V],
    {norm1,norm2}.scale [d],
    attn.wq [d, H*(nope+rope)], attn.wkv_a [d, kvr+rope],
    attn.kv_norm.scale [kvr], attn.wkv_b [kvr, H*(nope+v)],
    attn.wo [H*v, d],
    pre_blocks.0.mlp.{w_gate,w_up} [d, ff], w_down [ff, d],
    periods.l0.moe.w_router [d, router_experts],
    periods.l0.moe.{w_gate,w_up} [E, d, dff], w_down [E, dff, d],
    periods.l0.moe.shared.{w_gate,w_up} [d, shared*dff], w_down [shared*dff, d].

The plain model computes attention a block of queries at a time, so that
it fits at 8192 tokens, and every held expert on every token, weighted by
its gate weight (0 where the token is not routed to it).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import Arith, rmsnorm

# program ModelConfig field <- configuration file key
KEYS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
        "num_heads": "num_attention_heads",
        "num_kv_heads": "num_key_value_heads",
        "d_ff": "intermediate_size", "vocab_size": "vocab_size",
        "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "num_experts": "n_routed_experts", "router_experts": "router_experts",
        "num_shared_experts": "n_shared_experts",
        "top_k": "num_experts_per_tok", "moe_d_ff": "moe_intermediate_size",
        "norm_topk_prob": "norm_topk_prob",
        "first_dense": "first_k_dense_replace",
        "router_aux_coef": "aux_loss_alpha",
        "tie_embeddings": "tie_word_embeddings", "rope_theta": "rope_theta",
        "yarn_factor": "yarn_factor",
        "yarn_original_max_position": "yarn_original_max_position_embeddings",
        "yarn_mscale_all_dim": "yarn_mscale_all_dim",
        "param_dtype": "param_dtype", "compute_dtype": "compute_dtype"}
# top-level containers of the program's tree that are lists
LISTS = ("pre_blocks",)
PROGRAM_NORM_EPS = 1e-6
# the published rope_scaling key each top-level yarn_* key repeats
YARN = {"yarn_factor": "factor",
        "yarn_original_max_position_embeddings":
            "original_max_position_embeddings",
        "yarn_mscale_all_dim": "mscale_all_dim"}
# the ends of YaRN's ramp, which the program fixes as DeepSeek-V2 does
YARN_BETAS = {"beta_fast": 32, "beta_slow": 1}
Q_BLOCK = 1024                  # the plain attention's queries a block


def require(mc):
    """What the program's block fixes and takes no key for."""
    fixed = {"rms_norm_eps": PROGRAM_NORM_EPS, "hidden_act": "silu",
             "attention_bias": False, "q_lora_rank": None,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1,
             "moe_layer_freq": 1}
    wrong = {k: mc.get(k) for k, v in fixed.items() if mc.get(k) != v}
    if wrong:
        raise SystemExit(f"the program's MLA-MoE block has {fixed}; the "
                         f"configuration gives {wrong}")
    rs = mc["rope_scaling"]
    if rs.get("type") != "yarn" or any(mc[k] != rs[v]
                                       for k, v in YARN.items()):
        raise SystemExit("the top-level yarn_* keys must repeat the "
                         f"configuration's rope_scaling ({rs})")
    if any(rs[k] != v for k, v in YARN_BETAS.items()):
        raise SystemExit(f"the program's YaRN fixes {YARN_BETAS}; the "
                         f"configuration's rope_scaling gives {rs}")
    if rs["mscale"] != rs["mscale_all_dim"]:
        # DeepSeek-V2 scales cos and sin by the ratio of the two
        # temperatures; the program leaves them unscaled
        raise SystemExit("the program's YaRN needs mscale == mscale_all_dim, "
                         f"got {rs}")


# ------------------------------------------------------------------ weights
def _dims(mc):
    return {"d": mc["hidden_size"], "V": mc["vocab_size"],
            "H": mc["num_attention_heads"], "kvr": mc["kv_lora_rank"],
            "nope": mc["qk_nope_head_dim"], "rope": mc["qk_rope_head_dim"],
            "dv": mc["v_head_dim"], "ff": mc["intermediate_size"],
            "E": mc["n_routed_experts"], "R": mc["router_experts"],
            "k": mc["num_experts_per_tok"], "dff": mc["moe_intermediate_size"],
            "sh": mc["n_shared_experts"] * mc["moe_intermediate_size"],
            "dense": mc["first_k_dense_replace"],
            "L": mc["num_hidden_layers"] - mc["first_k_dense_replace"]}


def _attn_shapes(m, lead=()):
    d, H, kvr, rope = m["d"], m["H"], m["kvr"], m["rope"]
    qk, dv = m["nope"] + rope, m["dv"]
    return {"attn.wq": ((*lead, d, H * qk), "matrix"),
            "attn.wkv_a": ((*lead, d, kvr + rope), "matrix"),
            "attn.kv_norm.scale": ((*lead, kvr), "norm"),
            "attn.wkv_b": ((*lead, kvr, H * (m["nope"] + dv)), "matrix"),
            "attn.wo": ((*lead, H * dv, d), "matrix"),
            "norm1.scale": ((*lead, d), "norm"),
            "norm2.scale": ((*lead, d), "norm")}


def param_shapes(mc):
    """{path: (shape, kind)} of the program's parameter layout."""
    m = _dims(mc)
    d, V, L, E, dff, sh = m["d"], m["V"], m["L"], m["E"], m["dff"], m["sh"]
    out = {"embed": ((V, d), "embed"), "final_norm.scale": ((d,), "norm"),
           "lm_head": ((d, V), "matrix")}
    for i in range(m["dense"]):
        p = f"pre_blocks.{i}."
        out.update({p + k: v for k, v in _attn_shapes(m).items()})
        out.update({p + "mlp.w_gate": ((d, m["ff"]), "matrix"),
                    p + "mlp.w_up": ((d, m["ff"]), "matrix"),
                    p + "mlp.w_down": ((m["ff"], d), "matrix")})
    p = "periods.l0."
    out.update({p + k: v for k, v in _attn_shapes(m, (L,)).items()})
    out.update({
        p + "moe.w_router": ((L, d, m["R"]), "matrix"),
        p + "moe.w_gate": ((L, E, d, dff), "matrix"),
        p + "moe.w_up": ((L, E, d, dff), "matrix"),
        p + "moe.w_down": ((L, E, dff, d), "matrix"),
        p + "moe.shared.w_gate": ((L, d, sh), "matrix"),
        p + "moe.shared.w_up": ((L, d, sh), "matrix"),
        p + "moe.shared.w_down": ((L, sh, d), "matrix"),
    })
    return out


# ---------------------------------------------------------------- the model
def yarn_freqs(mc):
    """DeepSeek-V2's YaRN inverse frequencies of the rope dims: the base
    frequency where a dim turns more than beta_fast times over the original
    positions, the base over the factor where it turns fewer than
    beta_slow times, a linear ramp between."""
    dim, base = mc["qk_rope_head_dim"], float(mc["rope_theta"])
    rs = mc["rope_scaling"]

    def dim_of(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = 1.0 / (rs["factor"]
                   * base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(mc):
    """(q/k width)^-0.5 times YaRN's mscale(factor, mscale_all_dim)^2."""
    rs = mc["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


def _rope(x, freqs):
    """Rotate contiguous halves of x [B, S, h, rope] by position."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(mc, ar, h, a):
    B, S, _ = h.shape
    m = _dims(mc)
    H, nope, rope, dv, kvr = m["H"], m["nope"], m["rope"], m["dv"], m["kvr"]
    freqs = yarn_freqs(mc)
    q = ar.mm(h, a["wq"]).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freqs)], -1)
    kv_a = ar.mm(h, a["wkv_a"])
    c = rmsnorm(kv_a[..., :kvr], a["kv_norm"]["scale"], mc["rms_norm_eps"])
    k_pe = _rope(kv_a[..., kvr:].reshape(B, S, 1, rope), freqs)
    kv = ar.mm(c, a["wkv_b"]).reshape(B, S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, H, rope))], -1)
    v = kv[..., nope:]
    scale = jnp.asarray(softmax_scale(mc), h.dtype)
    bq = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = ar.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        allowed = jnp.arange(S)[None, :] <= i * bq + jnp.arange(bq)[:, None]
        s = jnp.where(allowed, s, jnp.asarray(-1e30, s.dtype))
        return ar.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(S // bq))            # [n, B, bq, H, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * dv)
    return ar.mm(o, a["wo"])


def _swiglu(ar, h, w_gate, w_up, w_down):
    return ar.mm(jax.nn.silu(ar.mm(h, w_gate)) * ar.mm(h, w_up), w_down)


def _experts(mc, ar, h, p):
    """(the expert layer's output, its balance term): the held experts on
    every token, each weighted by its gate weight, plus the shared
    experts."""
    B, S, d = h.shape
    m = _dims(mc)
    x = h.reshape(B * S, d)
    probs = jax.nn.softmax(ar.mm(x, p["w_router"]).astype(jnp.float32), -1)
    topw, tope = jax.lax.top_k(probs, m["k"])
    if mc["norm_topk_prob"]:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    routed = tope[..., None] == jnp.arange(m["R"])          # [T, k, R]
    gate = jnp.sum(jnp.where(routed, topw[..., None], 0.0), axis=1)
    g = ar.einsum("td,edf->tef", x, p["w_gate"])
    u = ar.einsum("td,edf->tef", x, p["w_up"])
    # sum_e gate_e (h_e @ W_e) as one product over e and f
    y = ar.einsum("tef,efd->td", jax.nn.silu(g) * u
                  * gate[:, :m["E"], None].astype(g.dtype), p["w_down"])
    s = p["shared"]
    y = y + _swiglu(ar, x, s["w_gate"], s["w_up"], s["w_down"])
    frac = jnp.sum(routed, axis=(0, 1)) / (B * S * m["k"])
    balance = m["R"] * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y.reshape(B, S, d), balance


def _block(mc, ar, x, p):
    eps = mc["rms_norm_eps"]
    x = x + _attention(mc, ar, rmsnorm(x, p["norm1"]["scale"], eps), p["attn"])
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    if "mlp" in p:
        m = p["mlp"]
        return x + _swiglu(ar, h, m["w_gate"], m["w_up"], m["w_down"]), 0.0
    y, balance = _experts(mc, ar, h, p["moe"])
    return x + y, balance


def loss(mc, precision, params, batch):
    """Mean next-token cross-entropy over the batch's masked positions,
    plus aux_loss_alpha times each expert layer's balance term."""
    ar = Arith(precision)
    x = params["embed"][batch["tokens"]]
    for p in params["pre_blocks"]:
        x, _ = jax.checkpoint(lambda x, p: _block(mc, ar, x, p))(x, p)
    body = jax.checkpoint(lambda x, p: _block(mc, ar, x, p))
    x, balance = jax.lax.scan(body, x, params["periods"]["l0"])
    aux = mc["aux_loss_alpha"] * jnp.sum(balance)
    x = rmsnorm(x, params["final_norm"]["scale"], mc["rms_norm_eps"])
    logits = ar.mm(x, params["lm_head"]).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return (jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            + aux)


# ------------------------------------------------------------------- counts
def _layer_matmul(m):
    """Weights a token multiplies in one layer's attention."""
    d, H, qk = m["d"], m["H"], m["nope"] + m["rope"]
    return (d * H * qk + d * (m["kvr"] + m["rope"])
            + m["kvr"] * H * (m["nope"] + m["dv"]) + H * m["dv"] * d)


def n_matmul(mc) -> int:
    """Weights in a matrix multiplication per token: each layer's latent
    attention; layer 0's SwiGLU; each expert layer's router, shared
    experts and, at the expected load under balanced routing, k·E/R of a
    routed expert (a token's k slots land on a held expert E/R of the
    time); and the head."""
    m = _dims(mc)
    d, dff = m["d"], m["dff"]
    dense = _layer_matmul(m) + 3 * d * m["ff"]
    moe = (_layer_matmul(m) + d * m["R"] + 3 * d * m["sh"]
           + m["k"] * m["E"] * 3 * d * dff // m["R"])
    return m["dense"] * dense + m["L"] * moe + d * m["V"]


def n_vector(mc) -> int:
    """Norm scales: elementwise, no matrix multiplication."""
    m = _dims(mc)
    return (m["dense"] + m["L"]) * (2 * m["d"] + m["kvr"]) + m["d"]


def n_gather(mc) -> int:
    """Weights a token reads into no matrix product: the untied embedding
    table, read by a gather, and the held experts it is not routed to at
    the balanced load (E - k·E/R experts' worth in each expert layer)."""
    m = _dims(mc)
    idle = (m["L"] * (m["E"] * m["R"] - m["k"] * m["E"]) * 3 * m["d"]
            * m["dff"] // m["R"])
    return mc["vocab_size"] * mc["hidden_size"] + idle


def n_params(mc) -> int:
    """Every parameter the chip holds, the held experts whole: n_matmul +
    n_vector + n_gather."""
    return sum(math.prod(s) for s, _ in param_shapes(mc).values())


def attention(mc):
    """The causal attention of the model: heads, kv heads, q/k width, v
    width and layers."""
    return {"heads": mc["num_attention_heads"],
            "kv_heads": mc["num_attention_heads"],
            "d_qk": mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"],
            "d_v": mc["v_head_dim"], "layers": mc["num_hidden_layers"]}


def expert_work(mc, tokens: int, backward: bool = False):
    """(FLOPs, HBM bytes) of the held experts' grouped products, all expert
    layers, over ``tokens`` tokens at the balanced load (k·E/R held rows a
    token): the gate, up and down products, at bf16 operands; with
    ``backward``, also their gradients, each product's input and weight
    gradient (twice the FLOPs). The bytes each product must move: its
    rows in, its weights in and its rows out, once."""
    m = _dims(mc)
    d, dff, E = m["d"], m["dff"], m["E"]
    rows = tokens * m["k"] * E / m["R"]
    flops = 3 * 2.0 * rows * d * dff
    weights = 3 * E * d * dff
    # gate and up: rows of d in, rows of dff out; down: dff in, d out
    acts = 2 * rows * (d + dff) + rows * (dff + d)
    moved = 2.0 * (weights + acts)
    if backward:
        flops *= 3
        # the input gradients move what the forward moved; the weight
        # gradients read the rows and the output gradients, write weights
        moved *= 3
    return flops * m["L"], moved * m["L"]
