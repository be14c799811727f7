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

Forward tiling (``fwd_tile_plan``): each grid step has a fixed cost, so the
forward takes the largest of 512/256/128 that divides each length (a
length up to 128 is one tile); 512 caps Mosaic's compile time. A tile the
mask empties in full (keys after every query under ``causal``, or all
``window`` or more behind) does no work, and its kv block index is clamped
to the nearest block its row of tiles runs, so the pipeline copies nothing
for it: at S=1024 causal, 3 of 4 tiles of 512 run. The running max ``m``
and sum ``l`` are [block_q, 128] scratch, replicated across lanes, so the
per-tile row reductions broadcast back without a lane/sublane relayout;
the output divide and the LSE write happen once, on the row's last tile
that runs.

Backward tiling (``bwd_tile_plan``): the same tiles from the lengths,
held to blocks of at most BWD_BLOCK_ELEMS elements at the head width, and
the same skipping in both passes. The dq pass (kv innermost) runs a q
row's ``_kv_span``; the dk/dv pass (q innermost) runs a kv column's
``_q_span``, and clamps the q, dO, LSE and delta blocks into it. Both read
the LSE and delta as [B*KV, G, Sq] f32 rows: the dq pass makes them
lane-replicated once a row, and the dk/dv pass takes its scores
transposed, so the rows broadcast down the sublanes as they are.

Forward and backward compile for TPU v5e as written
(``tests/test_tpu_compile.py``) and match ``ref.attention_ref`` on the
chip to bf16 rounding (``chip_smoke.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128            # the online-softmax state's lane width
FWD_BLOCKS = (512, 256, 128)
BWD_BLOCK_ELEMS = 512 * 1024   # the largest [tile, hd] block the backward takes


def _fwd_block(n):
    """The forward's tile along a sequence of length n: n itself up to 128,
    else the largest of FWD_BLOCKS that divides it (the kernel's domain is
    lengths up to 128 or multiples of 128)."""
    if n <= LANES:
        return n
    return next((b for b in FWD_BLOCKS if n % b == 0), LANES)


def fwd_tile_plan(Sq, Skv, *, causal=True, window=0, block_q=None,
                  block_kv=None):
    """The forward's tiles and how many of them run, per (batch, head):
    (block_q, block_kv, tiles_run, tiles_total). Tiles come from the
    lengths unless given; a tile the mask empties in full is skipped."""
    block_q = _fwd_block(Sq) if block_q is None else min(block_q, Sq)
    block_kv = _fwd_block(Skv) if block_kv is None else min(block_kv, Skv)
    return _plan(Sq, Skv, block_q, block_kv, causal=causal, window=window)


def _bwd_block(n, hd):
    """The backward's tile along a sequence of length n at head width hd:
    as ``_fwd_block``, among the tiles whose [tile, hd] blocks hold at most
    BWD_BLOCK_ELEMS elements (the dk/dv pass fits the default scoped VMEM
    of a v5e at 512 x 1024 and 256 x 2048, not at 512 x 2048)."""
    if n <= LANES:
        return n
    return next((b for b in FWD_BLOCKS
                 if n % b == 0 and b * hd <= BWD_BLOCK_ELEMS), LANES)


def bwd_tile_plan(Sq, Skv, hd, *, causal=True, window=0, block_q=None,
                  block_kv=None):
    """The backward's tiles and how many of them run, per (batch, head), for
    the dq pass and the dk/dv pass: ((block_q, block_kv, tiles_run,
    tiles_total), same for dk/dv). Tiles come from the lengths and the
    head width unless given; a tile the mask empties in full is skipped."""
    block_q = _bwd_block(Sq, hd) if block_q is None else min(block_q, Sq)
    block_kv = _bwd_block(Skv, hd) if block_kv is None else min(block_kv, Skv)
    mask = dict(causal=causal, window=window)
    return (_plan(Sq, Skv, block_q, block_kv, **mask),
            _plan(Sq, Skv, block_q, block_kv, kv_columns=True, **mask))


def _plan(Sq, Skv, block_q, block_kv, *, causal, window, kv_columns=False):
    """(block_q, block_kv, tiles_run, tiles_total): the tiles in the q-row
    spans of ``_kv_span``, or with ``kv_columns`` in the kv-column spans
    of ``_q_span``."""
    nq, nkv = Sq // block_q, Skv // block_kv
    span, n, nother = (_q_span, nkv, nq) if kv_columns else \
        (_kv_span, nq, nkv)
    run = 0
    for i in range(n):
        first, last = span(i, nother, block_q=block_q, block_kv=block_kv,
                           causal=causal, window=window, q_offset=Skv - Sq,
                           xp=np)
        run += max(int(last) - int(first) + 1, 0)
    return block_q, block_kv, run, nq * nkv


def _kv_span(iq, nkv, *, block_q, block_kv, causal, window, q_offset,
             xp=jnp):
    """First and last kv block that row ``iq`` of q tiles needs: every tile
    outside [first, last] is masked in full (keys after the row's last
    query under ``causal``, keys ``window`` or more behind its first query).
    Scalar arithmetic on a program id (``xp=jnp``) or on ints (``xp=np``)."""
    first, last = 0, nkv - 1
    if window:
        first = xp.maximum(q_offset + iq * block_q - window + 1, 0) // block_kv
    if causal:
        last = xp.minimum(last, (q_offset + (iq + 1) * block_q - 1) // block_kv)
    return first, last


def _q_span(ik, nq, *, block_q, block_kv, causal, window, q_offset, xp=jnp):
    """First and last q block that sees column ``ik`` of kv tiles, the
    mirror of ``_kv_span``: queries before the column's first key are
    masked under ``causal``, queries ``window`` or more after its last key
    under ``window``. ``last < first`` where no query sees the column
    (a window with Sq < Skv)."""
    first, last = 0, nq - 1
    if causal:
        first = xp.maximum(ik * block_kv - q_offset, 0) // block_q
    if window:
        last = xp.minimum(
            last, ((ik + 1) * block_kv + window - 2 - q_offset) // block_q)
    return first, last


def _clamp(i, first, last):
    """Grid index ``i`` held to [first, max(first, last)]: a skipped step
    maps to a block its neighbours run, so the pipeline copies nothing."""
    return jnp.minimum(jnp.maximum(i, first), jnp.maximum(first, last))


def _lanes(x, n):
    """A lane-replicated [rows, 128] state as [rows, n]: whole vregs
    repeated, or lanes cut, so no row vector changes layout."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _mask(block_q, block_kv, iq, ik, *, causal, window, q_offset,
          kv_rows=False):
    """The tile's [block_q, block_kv] mask, or with ``kv_rows`` the
    [block_kv, block_q] mask of the transposed scores."""
    shape, q_axis = ((block_kv, block_q), 1) if kv_rows else \
        ((block_q, block_kv), 0)
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_axis)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                     1 - q_axis)
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    return mask


def _kernel_fwd(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                causal, window, scale, block_q, block_kv, nkv, q_offset):
    """Online-softmax forward over the kv tiles of one q tile; emits the
    output and LSE = m + log(l) per query row (read by the backward)."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    first, last = _kv_span(iq, nkv, block_q=block_q, block_kv=block_kv,
                           causal=causal, window=window, q_offset=q_offset)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((ik >= first) & (ik <= last))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                       # [bk, hd]
        v = v_ref[0].astype(jnp.float32)                       # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        mask = _mask(block_q, block_kv, iq, ik, causal=causal, window=window,
                     q_offset=q_offset)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                    # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, block_kv))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    @pl.when(ik == last)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])
                       ).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _kernel_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, lse_sc, delta_sc, *, causal, window, scale, block_q,
               block_kv, nkv, q_offset):
    """dq = sum_kv (P o (dP - delta)) K * scale, P = exp(S - LSE).
    Grid: (BKV, G, nq, nkv); kv innermost, accumulated in VMEM scratch over
    the row's kv tiles that the mask leaves a key in. The row's LSE and
    delta are made lane-replicated once, on its first tile."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    first, last = _kv_span(iq, nkv, block_q=block_q, block_kv=block_kv,
                           causal=causal, window=window, q_offset=q_offset)

    @pl.when(ik == first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lse_sc[...] = jnp.broadcast_to(lse_ref[0, 0][:, None], lse_sc.shape)
        delta_sc[...] = jnp.broadcast_to(delta_ref[0, 0][:, None],
                                         delta_sc.shape)

    @pl.when((ik >= first) & (ik <= last))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                       # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        mask = _mask(block_q, block_kv, iq, ik, causal=causal, window=window,
                     q_offset=q_offset)
        p = jnp.where(mask, jnp.exp(s - _lanes(lse_sc[...], block_kv)), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta_sc[...], block_kv))
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == last)
    def _done():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _kernel_dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal, window, scale, block_q, block_kv, nq, q_offset):
    """dk/dv for one kv tile; grid (BKV, G, nkv, nq) with q innermost, over
    the q tiles that see the kv tile. Scores are taken transposed,
    S^T = K Q^T [bk, bq], so the LSE and delta rows broadcast down the
    sublanes and every product is plain or against a transposed right
    operand: dv = P^T dO ; dk = dS^T Q * scale. A kv tile that no query
    sees writes zeros."""
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    first, last = _q_span(ik, nq, block_q=block_q, block_kv=block_kv,
                          causal=causal, window=window, q_offset=q_offset)

    @pl.when(iq == first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((iq >= first) & (iq <= last))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                       # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bk, bq]
        mask = _mask(block_q, block_kv, iq, ik, causal=causal, window=window,
                     q_offset=q_offset, kv_rows=True)
        pt = jnp.where(mask, jnp.exp(st - lse_ref[0]), 0.0)   # lse [1, bq]
        dv_acc[...] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0])
        dk_acc[...] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == jnp.maximum(first, last))
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
                               block_q=None, block_kv=None, interpret=False):
    """Returns (out [B,Sq,H,hd], lse [B*KV, G, Sq]). Tiles from
    ``fwd_tile_plan`` unless given."""
    qr, kr, vr, dims = _layout(q, k, v)
    B, Sq, H, hd, Skv, KV, G = dims
    if scale is None:
        scale = hd ** -0.5
    assert not causal or Sq <= Skv, "causal queries need keys up to them"
    block_q, block_kv, _, _ = fwd_tile_plan(Sq, Skv, causal=causal,
                                            window=window, block_q=block_q,
                                            block_kv=block_kv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    nq, nkv = Sq // block_q, Skv // block_kv
    tiles = dict(causal=causal, window=window, block_q=block_q,
                 block_kv=block_kv, nkv=nkv, q_offset=Skv - Sq)
    span = functools.partial(_kv_span, **tiles)

    def kv_map(b, g, iq, ik):
        # a skipped tile maps to the nearest block its row runs: no copy
        first, last = span(iq)
        return (b, jnp.minimum(jnp.maximum(ik, first), last), 0)

    kernel = functools.partial(_kernel_fwd, scale=scale, **tiles)

    with jax.named_scope("kernel.flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            name="kernel.flash_fwd",
            grid=(B * KV, G, nq, nkv),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, hd), lambda b, g, iq, ik: (b, g, iq, 0)),
                pl.BlockSpec((1, block_kv, hd), kv_map),
                pl.BlockSpec((1, block_kv, hd), kv_map),
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
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
            interpret=interpret,
        )(qr, kr, vr)
    return _unlayout_q(out, dims), lse


def flash_attention_bwd_pallas(q, k, v, out, lse, do, *, causal=True, window=0,
                               scale=None, block_q=None, block_kv=None,
                               interpret=False):
    """Two-pass flash backward: (dq, dk, dv), all like their primals. Tiles
    from ``bwd_tile_plan`` unless given; each pass skips the tiles the mask
    empties, and its skipped steps map to blocks it runs, so they copy
    nothing."""
    qr, kr, vr, dims = _layout(q, k, v)
    B, Sq, H, hd, Skv, KV, G = dims
    or_, dor = (_layout(out, k, v)[0], _layout(do, k, v)[0])
    if scale is None:
        scale = hd ** -0.5
    dq_plan, dkv_plan = bwd_tile_plan(Sq, Skv, hd, causal=causal,
                                      window=window, block_q=block_q,
                                      block_kv=block_kv)
    mask = dict(causal=causal, window=window, q_offset=Skv - Sq)

    # delta = rowsum(dO o O) — tiny, compute with jnp
    delta = jnp.sum(dor.astype(jnp.float32) * or_.astype(jnp.float32), axis=-1)

    # dq: q block outer, kv block inner (sequential) so dq accumulates
    bq, bk = dq_plan[:2]
    nq, nkv = Sq // bq, Skv // bk
    tiles = dict(block_q=bq, block_kv=bk, **mask)
    kv_span = functools.partial(_kv_span, nkv=nkv, **tiles)

    def kv_map(b, g, iq, ik):
        return (b, _clamp(ik, *kv_span(iq)), 0)

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, g, iq, ik: (b, g, iq, 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, g, iq, ik: (b, g, iq))
    kv_spec = pl.BlockSpec((1, bk, hd), kv_map)

    with jax.named_scope("kernel.flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_kernel_dq, scale=scale, nkv=nkv, **tiles),
            name="kernel.flash_bwd_dq",
            grid=(B * KV, G, nq, nkv),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B * KV, G, Sq, hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                            pltpu.VMEM((bq, LANES), jnp.float32),
                            pltpu.VMEM((bq, LANES), jnp.float32)],
            interpret=interpret,
        )(qr, kr, vr, dor, lse, delta)

    # dk/dv: kv block outer, q block inner (sequential) so dk/dv accumulate
    bq, bk = dkv_plan[:2]
    nq, nkv = Sq // bq, Skv // bk
    tiles = dict(block_q=bq, block_kv=bk, **mask)
    q_span = functools.partial(_q_span, nq=nq, **tiles)

    def q_map(b, g, ik, iq):
        return (b, g, _clamp(iq, *q_span(ik)), 0)

    def row_map(b, g, ik, iq):
        return (b, g, _clamp(iq, *q_span(ik)))

    q_spec = pl.BlockSpec((1, 1, bq, hd), q_map)
    row_spec = pl.BlockSpec((1, 1, bq), row_map)
    kv_spec = pl.BlockSpec((1, bk, hd), lambda b, g, ik, iq: (b, ik, 0))

    # the out block (b, ik) is revisited once per q-head group g with other
    # ik blocks in between, so cross-g accumulation can't live in VMEM
    # scratch — run one call per group and sum (G is small: <= 8 for the
    # assigned archs). G==1 (MHA after grouping) needs a single call.
    def _dkv_call(qg, dog, lseg, deltag):
        with jax.named_scope("kernel.flash_bwd_dkv"):
            return pl.pallas_call(
                functools.partial(_kernel_dkv, scale=scale, nq=nq, **tiles),
                name="kernel.flash_bwd_dkv",
                grid=(B * KV, 1, nkv, nq),
                in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec,
                          row_spec],
                out_specs=[kv_spec, kv_spec],
                out_shape=[
                    jax.ShapeDtypeStruct((B * KV, Skv, hd), jnp.float32),
                    jax.ShapeDtypeStruct((B * KV, Skv, hd), jnp.float32)],
                scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                                pltpu.VMEM((bk, hd), jnp.float32)],
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
                           scale=None, block_q=None, block_kv=None,
                           interpret=False):
    """q: [B,Sq,H,hd]; k/v: [B,Skv,KV,hd]. Returns [B,Sq,H,hd].

    Differentiable: forward saves per-row LSE; backward runs the two-pass
    flash backward kernels (dq then dk/dv). Given tiles serve both passes;
    by default the forward takes ``fwd_tile_plan``'s and the backward
    ``bwd_tile_plan``'s."""
    assert kv_len is None, "flash path assumes a full kv sequence"
    fwd = functools.partial(
        flash_attention_fwd_pallas, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret)

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _fa(q, k, v):
        return fwd(q, k, v)[0]

    def _fwd(q, k, v):
        out, lse = fwd(q, k, v)
        return out, (q, k, v, out, lse)

    def _bwd(res, do):
        q, k, v, out, lse = res
        return flash_attention_bwd_pallas(
            q, k, v, out, lse, do, causal=causal, window=window, scale=scale,
            block_q=block_q, block_kv=block_kv, interpret=interpret)

    _fa.defvjp(_fwd, _bwd)
    return _fa(q, k, v)
