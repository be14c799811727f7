"""Mixture-of-Experts FFN: a router over every expert, and a dropless
grouped product over the experts this layer holds.

A layer holds ``cfg.num_experts`` experts: experts 0..num_experts-1 of the
``cfg.router_width`` that its router scores (under expert parallelism, the
share of the chip at index 0; all of them where the two are equal). Each
token's top-k slots are sorted by expert, so the held experts' slots form
one contiguous group each, and one grouped matrix product a projection
computes every group whatever its load (``kernels.ops.expert_ffn``): no
slot is dropped. A slot routed to an expert that is not held adds
nothing here; under expert parallelism the chip that holds it adds its
part. The shared experts run on every token.

Three named scopes let a profile name the layer's parts:
``kernel.moe_route`` (router, top-k, sort and the gather of the slots'
rows), ``kernel.moe_gmm`` (the grouped products) and ``kernel.moe_combine``
(un-permute, and each token's weighted sum of its slots).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.layers import dense_init, init_swiglu, swiglu_apply
from repro.utils import fold_in_name

# the layer's counters, as a forward's loss metrics and a round's stats
# carry them: rows the held experts computed (every slot routed to them:
# none is dropped) add up over layers and forwards; the load ratio keeps
# the largest
COUNTERS = ("moe_held_rows", "moe_load_ratio")


def add_counts(a, b):
    """The counters (and any other additive entry, such as a loss) of ``a``
    and ``b`` together; an entry of ``a`` that ``b`` lacks stays as it is."""
    return {k: (jnp.maximum if k == "moe_load_ratio" else jnp.add)(v, b[k])
            if k in b else v for k, v in a.items()}


def fold_counts(counts):
    """``add_counts`` over the leading axis of stacked counters."""
    return {k: (jnp.max if k == "moe_load_ratio" else jnp.sum)(v, axis=0)
            for k, v in counts.items()}


def init_moe(key, cfg):
    d, E, dff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = {n: fold_in_name(key, n) for n in ("router", "gate", "up", "down", "shared")}
    p = {
        "w_router": dense_init(ks["router"], (d, cfg.router_width),
                               jnp.float32),                  # router in fp32
        "w_gate": dense_init(ks["gate"], (E, d, dff), cfg.pdtype),
        "w_up": dense_init(ks["up"], (E, d, dff), cfg.pdtype),
        "w_down": dense_init(ks["down"], (E, dff, d), cfg.pdtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_swiglu(ks["shared"], d, cfg.num_shared_experts * dff,
                                  cfg.pdtype)
    return p


def moe_apply(p, x, cfg):
    """x: [B,S,d] -> (y, aux). aux: ``lb_loss`` (the balance term over the
    router's full width), ``held_rows`` (slots the held experts computed)
    and ``load_ratio`` (the most loaded held expert's rows over the held
    experts' mean)."""
    B, S, d = x.shape
    E, R, k = cfg.num_experts, cfg.router_width, cfg.top_k
    cd = cfg.cdtype
    T = B * S
    xf = x.reshape(T, d)

    with jax.named_scope("kernel.moe_route"):
        logits = jnp.dot(xf.astype(jnp.float32), p["w_router"],
                         precision=jax.lax.Precision.HIGHEST)    # [T,R]
        probs = jax.nn.softmax(logits, axis=-1)
        topw, tope = jax.lax.top_k(probs, k)                      # [T,k]
        if cfg.norm_topk_prob:
            topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True),
                                      1e-9)
        counts = jnp.sum(tope.reshape(-1, 1) == jnp.arange(R), axis=0,
                         dtype=jnp.int32)                         # [R]
        sizes = counts[:E]                                        # held rows

    y = kops.expert_ffn(xf.astype(cd), tope, topw.astype(cd),
                        p["w_gate"].astype(cd), p["w_up"].astype(cd),
                        p["w_down"].astype(cd),
                        expert_parallel=cfg.expert_parallel).astype(cd)

    if cfg.num_shared_experts:
        y = y + swiglu_apply(p["shared"], xf.astype(cd), cd)

    frac = counts.astype(jnp.float32) / (T * k)                   # f_e
    imp = jnp.mean(probs, axis=0)                                 # P_e
    held = jnp.sum(sizes)
    aux = {"lb_loss": R * jnp.sum(frac * imp),
           "held_rows": held,
           "load_ratio": (E * jnp.max(sizes)).astype(jnp.float32)
           / jnp.maximum(held, 1).astype(jnp.float32)}
    return y.reshape(B, S, d), aux
