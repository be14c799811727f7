"""Operations and bytes that a round requires, from the cell's shapes.

The model-FLOP arithmetic is the program's ``benchmarks/roofline.py``
rule (6·N·D for a trained token, 2·N·D for a forward-only token), with N
the parameters that take part in a matrix multiplication per token
(``n_matmul`` of the configuration's family, ``bench/families/<family>.py``),
and causal attention's score and value products added on top, from the
heads and widths that the family's ``attention`` gives. Recomputation
under remat is never counted: these are the operations the round needs,
not the ones the program happens to run.
"""
from __future__ import annotations


def causal_pairs(S: int) -> int:
    return S * (S + 1) // 2


def attn_fwd_flops(att, B: int, S: int) -> float:
    """Scores and weighted values of causal attention, all layers, one
    forward over a [B, S] batch: per pair and head, 2 FLOPs a q/k width
    for the score and 2 a v width for the weighted value. ``att`` is the
    family's ``attention(mc)``."""
    return (2.0 * B * att["heads"] * causal_pairs(S)
            * (att["d_qk"] + att["d_v"]) * att["layers"])


def attn_bwd_flops(att, B: int, S: int) -> float:
    """The backward pass needs four such products (dV, dP, dQ, dK)."""
    return 2.0 * attn_fwd_flops(att, B, S)


def attn_fwd_bytes(att, B: int, S: int, itemsize: int = 2) -> float:
    """HBM bytes the forward kernel must move, all layers: q and k in at
    the q/k width, v in and the output out at the v width, and the f32
    log-sum-exp row out."""
    H, KV, dqk, dv = att["heads"], att["kv_heads"], att["d_qk"], att["d_v"]
    q_side = B * S * H * (dqk + dv) * itemsize       # q in, out
    kv_side = B * S * KV * (dqk + dv) * itemsize     # k, v in
    return float(att["layers"] * (q_side + kv_side + B * H * S * 4))


def attn_bwd_bytes(att, B: int, S: int, itemsize: int = 2) -> float:
    """Backward, all layers: q, k, v, out, dout in; dq, dk, dv out; the f32
    log-sum-exp and row-sum rows in."""
    H, KV, dqk, dv = att["heads"], att["kv_heads"], att["d_qk"], att["d_v"]
    q_side = B * S * H * (2 * dqk + 2 * dv) * itemsize   # q, dq; out, dout
    kv_side = B * S * KV * (2 * dqk + 2 * dv) * itemsize  # k, dk; v, dv
    return float(att["layers"] * (q_side + kv_side + 2 * B * H * S * 4))


def round_work(family, mc, traffic, trained: int,
               train_calls: int | None = None):
    """Required model FLOPs, and the attention kernels' FLOPs and bytes, of
    one round.

    ``trained`` clients (gate > 0) each run E steps on ``per_client`` x
    ``seq`` tokens; every client and the server batch get one forward (the
    eval pre-pass and the server loss). ``train_calls`` is how many clients
    the program trains (the spatial round trains gated-out clients too):
    the kernels' work counts those, the model FLOPs only ``trained``."""
    C, b, S, E = (traffic["clients"], traffic["per_client"], traffic["seq"],
                  traffic["local_steps"])
    N = family.n_matmul(mc)
    att = family.attention(mc)
    train_calls = trained if train_calls is None else train_calls
    trained_tokens = trained * E * b * S
    eval_tokens = (C + 1) * b * S
    fwd, bwd = attn_fwd_flops(att, b, S), attn_bwd_flops(att, b, S)
    model = (trained_tokens * 6.0 * N + trained * E * (fwd + bwd)
             + eval_tokens * 2.0 * N + (C + 1) * fwd)
    attn_flops = (C + 1) * fwd + train_calls * E * (fwd + bwd)
    attn_bytes = ((C + 1) * attn_fwd_bytes(att, b, S)
                  + train_calls * E * (attn_fwd_bytes(att, b, S)
                                       + attn_bwd_bytes(att, b, S)))
    return {"model_flops": model, "trained_tokens": trained_tokens,
            "attn_flops": attn_flops, "attn_bytes": attn_bytes}
