"""Pallas TPU flash attention (blockwise online softmax, GQA).

Target: TPU v5e MXU. Tiling: queries in ``block_q`` rows, keys/values in
``block_kv`` rows, one (batch x kv-head x q-group) per grid cell; the kv
dimension is the innermost (sequential) grid axis so the m/l/acc online-
softmax state lives in VMEM scratch across kv blocks.

Layout notes (HBM->VMEM):
  q   [B*KV, G, Sq, hd]   block (1, 1, block_q, hd)
  k,v [B*KV, Skv, hd]     block (1, block_kv, hd)
  out like q.
hd is expected to be 64/96/128 (lane-aligned); block_q/block_kv multiples
of 128 keep the MXU fed on the s = q @ k^T and p @ v contractions.

Forward and backward compile for TPU v5e as written
(``tests/test_tpu_compile.py``) and match ``ref.attention_ref`` on the
chip to bf16 rounding (``chip_smoke.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, scale, block_q, block_kv, nkv, q_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                       # [bk, hd]
    v = v_ref[0].astype(jnp.float32)                       # [bk, hd]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nkv - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _kernel_fwd_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                    causal, window, scale, block_q, block_kv, nkv, q_offset):
    """Forward kernel variant that also emits LSE = m + log(l) per query row
    (needed by the backward pass)."""
    ik = pl.program_id(3)
    _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            causal=causal, window=window, scale=scale, block_q=block_q,
            block_kv=block_kv, nkv=nkv, q_offset=q_offset)

    @pl.when(ik == nkv - 1)
    def _emit_lse():
        lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _mask(block_q, block_kv, iq, ik, *, causal, window, q_offset):
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    return mask


def _kernel_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
               causal, window, scale, block_q, block_kv, nkv, q_offset):
    """dq = sum_kv (P o (dP - delta)) K * scale, P = exp(S - LSE).
    Grid: (BKV, G, nq, nkv); kv innermost, accumulated in VMEM scratch."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _mask(block_q, block_kv, iq, ik, causal=causal, window=window,
                 q_offset=q_offset)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ik == nkv - 1)
    def _done():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _kernel_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal, window, scale, block_q, block_kv, nq, q_offset):
    """dk/dv for one kv block; grid (BKV, G, nkv, nq) with q innermost.
    dv = P^T dO ; dk = dS^T Q * scale."""
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _mask(block_q, block_kv, iq, ik, causal=causal, window=window,
                 q_offset=q_offset)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)          # [bq, bk]
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _layout(q, k, v):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qr = q.transpose(0, 2, 1, 3).reshape(B, KV, G, Sq, hd).reshape(B * KV, G, Sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    return qr, kr, vr, (B, Sq, H, hd, Skv, KV, G)


def _unlayout_q(x, dims):
    B, Sq, H, hd, Skv, KV, G = dims
    return x.reshape(B, KV, G, Sq, hd).transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


def flash_attention_fwd_pallas(q, k, v, *, causal=True, window=0, scale=None,
                               block_q=128, block_kv=128, interpret=False):
    """Returns (out [B,Sq,H,hd], lse [B*KV, G, Sq])."""
    qr, kr, vr, dims = _layout(q, k, v)
    B, Sq, H, hd, Skv, KV, G = dims
    if scale is None:
        scale = hd ** -0.5
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    nq, nkv = Sq // block_q, Skv // block_kv

    kernel = functools.partial(
        _kernel_fwd_lse, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, nkv=nkv, q_offset=Skv - Sq)

    with jax.named_scope("kernel.flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            name="kernel.flash_fwd",
            grid=(B * KV, G, nq, nkv),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, hd), lambda b, g, iq, ik: (b, g, iq, 0)),
                pl.BlockSpec((1, block_kv, hd), lambda b, g, iq, ik: (b, ik, 0)),
                pl.BlockSpec((1, block_kv, hd), lambda b, g, iq, ik: (b, ik, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, hd), lambda b, g, iq, ik: (b, g, iq, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, g, iq, ik: (b, g, iq)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * KV, G, Sq, hd), q.dtype),
                jax.ShapeDtypeStruct((B * KV, G, Sq), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
            interpret=interpret,
        )(qr, kr, vr)
    return _unlayout_q(out, dims), lse


def flash_attention_bwd_pallas(q, k, v, out, lse, do, *, causal=True, window=0,
                               scale=None, block_q=128, block_kv=128,
                               interpret=False):
    """Two-pass flash backward: (dq, dk, dv), all like their primals."""
    qr, kr, vr, dims = _layout(q, k, v)
    B, Sq, H, hd, Skv, KV, G = dims
    or_, dor = (_layout(out, k, v)[0], _layout(do, k, v)[0])
    if scale is None:
        scale = hd ** -0.5
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    nq, nkv = Sq // block_q, Skv // block_kv
    q_offset = Skv - Sq

    # delta = rowsum(dO o O) — tiny, compute with jnp
    delta = jnp.sum(dor.astype(jnp.float32) * or_.astype(jnp.float32), axis=-1)

    common = dict(causal=causal, window=window, scale=scale,
                  block_q=block_q, block_kv=block_kv, q_offset=q_offset)

    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, g, i, j: (b, g, i, 0))
    kv_spec_q = pl.BlockSpec((1, block_kv, hd), lambda b, g, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, g, i, j: (b, g, i))

    with jax.named_scope("kernel.flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_kernel_dq, nkv=nkv, **common),
            name="kernel.flash_bwd_dq",
            grid=(B * KV, G, nq, nkv),
            in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec,
                      row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B * KV, G, Sq, hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
            interpret=interpret,
        )(qr, kr, vr, dor, lse, delta)

    # dk/dv: kv block outer, q block inner (sequential) so dk/dv accumulate
    q_spec2 = pl.BlockSpec((1, 1, block_q, hd), lambda b, g, j, i: (b, g, i, 0))
    kv_spec2 = pl.BlockSpec((1, block_kv, hd), lambda b, g, j, i: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q), lambda b, g, j, i: (b, g, i))

    # dk/dv: the out block (b, j) is revisited once per q-head group g with
    # other j blocks in between, so cross-g accumulation can't live in VMEM
    # scratch — run one call per group and sum (G is small: <= 8 for the
    # assigned archs). G==1 (MHA after grouping) needs a single call.
    def _dkv_call(qg, dog, lseg, deltag):
        with jax.named_scope("kernel.flash_bwd_dkv"):
            return pl.pallas_call(
                functools.partial(_kernel_dkv, nq=nq, **common),
                name="kernel.flash_bwd_dkv",
                grid=(B * KV, 1, nkv, nq),
                in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                          row_spec2],
                out_specs=[kv_spec2, kv_spec2],
                out_shape=[
                    jax.ShapeDtypeStruct((B * KV, Skv, hd), jnp.float32),
                    jax.ShapeDtypeStruct((B * KV, Skv, hd), jnp.float32)],
                scratch_shapes=[pltpu.VMEM((block_kv, hd), jnp.float32),
                                pltpu.VMEM((block_kv, hd), jnp.float32)],
                interpret=interpret,
            )(qg, kr, vr, dog, lseg, deltag)

    dk_g = jnp.zeros((B * KV, Skv, hd), jnp.float32)
    dv_g = jnp.zeros((B * KV, Skv, hd), jnp.float32)
    for g in range(G):
        dk1, dv1 = _dkv_call(qr[:, g:g + 1], dor[:, g:g + 1],
                             lse[:, g:g + 1], delta[:, g:g + 1])
        dk_g = dk_g + dk1
        dv_g = dv_g + dv1

    dq = _unlayout_q(dq, dims)
    dk = dk_g.reshape(B, KV, Skv, hd).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_g.reshape(B, KV, Skv, hd).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


def flash_attention_pallas(q, k, v, *, causal=True, window=0, kv_len=None,
                           scale=None, block_q=128, block_kv=128, interpret=False):
    """q: [B,Sq,H,hd]; k/v: [B,Skv,KV,hd]. Returns [B,Sq,H,hd].

    Differentiable: forward saves per-row LSE; backward runs the two-pass
    flash backward kernels (dq then dk/dv)."""
    assert kv_len is None, "flash path assumes a full kv sequence"

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _fa(q, k, v):
        out, _ = flash_attention_fwd_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret)
        return out

    def _fwd(q, k, v):
        out, lse = flash_attention_fwd_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret)
        return out, (q, k, v, out, lse)

    def _bwd(res, do):
        q, k, v, out, lse = res
        return flash_attention_bwd_pallas(
            q, k, v, out, lse, do, causal=causal, window=window, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret)

    _fa.defvjp(_fwd, _bwd)
    return _fa(q, k, v)
