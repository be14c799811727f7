"""Operations and bytes that a round requires, from the cell's shapes.

The model-FLOP arithmetic is the program's ``benchmarks/roofline.py``
rule (6·N·D for a trained token, 2·N·D for a forward-only token), with N
the parameters that take part in a matrix multiplication per token, and
causal attention's score and value products added on top. Recomputation
under remat is never counted: these are the operations the round needs,
not the ones the program happens to run.
"""
from __future__ import annotations


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


def causal_pairs(S: int) -> int:
    return S * (S + 1) // 2


def attn_fwd_flops(mc, B: int, S: int) -> float:
    """Scores and weighted values of causal attention, all layers, one
    forward over a [B, S] batch: two products of 2 FLOPs a pair per head
    dimension."""
    d, H, KV, hd, ff, V, L = _dims(mc)
    return 4.0 * B * H * hd * causal_pairs(S) * L


def attn_bwd_flops(mc, B: int, S: int) -> float:
    """The backward pass needs four such products (dV, dP, dQ, dK)."""
    return 2.0 * attn_fwd_flops(mc, B, S)


def attn_fwd_bytes(mc, B: int, S: int, itemsize: int = 2) -> float:
    """HBM bytes the forward kernel must move, all layers: q, k, v in, the
    output out, and the f32 log-sum-exp row out."""
    d, H, KV, hd, ff, V, L = _dims(mc)
    qo = 2 * B * S * H * hd * itemsize
    kv = 2 * B * S * KV * hd * itemsize
    return float(L * (qo + kv + B * H * S * 4))


def attn_bwd_bytes(mc, B: int, S: int, itemsize: int = 2) -> float:
    """Backward, all layers: q, k, v, out, dout in; dq, dk, dv out; the f32
    log-sum-exp and row-sum rows in."""
    d, H, KV, hd, ff, V, L = _dims(mc)
    q_like = 4 * B * S * H * hd * itemsize        # q, out, dout in; dq out
    kv_like = 4 * B * S * KV * hd * itemsize      # k, v in; dk, dv out
    return float(L * (q_like + kv_like + 2 * B * H * S * 4))


def round_work(mc, traffic, trained: int, train_calls: int | None = None):
    """Required model FLOPs, and the attention kernels' FLOPs and bytes, of
    one round.

    ``trained`` clients (gate > 0) each run E steps on ``per_client`` x
    ``seq`` tokens; every client and the server batch get one forward (the
    eval pre-pass and the server loss). ``train_calls`` is how many clients
    the program trains (the spatial round trains gated-out clients too):
    the kernels' work counts those, the model FLOPs only ``trained``."""
    C, b, S, E = (traffic["clients"], traffic["per_client"], traffic["seq"],
                  traffic["local_steps"])
    N = n_matmul(mc)
    train_calls = trained if train_calls is None else train_calls
    trained_tokens = trained * E * b * S
    eval_tokens = (C + 1) * b * S
    fwd, bwd = attn_fwd_flops(mc, b, S), attn_bwd_flops(mc, b, S)
    model = (trained_tokens * 6.0 * N + trained * E * (fwd + bwd)
             + eval_tokens * 2.0 * N + (C + 1) * fwd)
    attn_flops = (C + 1) * fwd + train_calls * E * (fwd + bwd)
    attn_bytes = ((C + 1) * attn_fwd_bytes(mc, b, S)
                  + train_calls * E * (attn_fwd_bytes(mc, b, S)
                                       + attn_bwd_bytes(mc, b, S)))
    return {"model_flops": model, "trained_tokens": trained_tokens,
            "attn_flops": attn_flops, "attn_bytes": attn_bytes}

