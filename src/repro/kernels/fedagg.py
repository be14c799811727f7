"""Pallas TPU kernels for FedALIGN's gated client aggregation.

The base reduction is the paper's server step (eq. (15)): given C client
updates (flattened to [C, M]), data fractions p_k and inclusion gates I_k,

    out[m] = sum_k p_k I_k u[k, m] / sum_k p_k I_k

The parameter axis M is tiled in ``block_m`` columns; each grid cell loads a
[C, block_m] update slab into VMEM plus the per-client [C, 1] weight/gate
columns, and emits one [1, block_m] output row. Every operand is 2-D
(Mosaic lays out only 2-D tiles), and the client reduction is a sublane
sum on the VPU. Memory-bound (arithmetic intensity ~= 1 FLOP/byte), so
block_m is a multiple of 128 lanes sized for DMA efficiency.

Robust / private variants are FUSED INTO THE SAME GRID CELL — the [C, bm]
slab is already in VMEM, so a coordinate-wise select (``trimmed_mean``,
``median``), a per-client clip scale + noise add (``dp``), or a gate rewrite
(``cosine_filter``, handled upstream as a gate pre-pass) costs ~0 extra HBM
traffic versus a second pass over the parameters:

- ``trimmed_mean`` / ``median`` rank each column over the client axis
  (``order_stat_reduce``; excluded clients keyed to +inf so the n included
  values take ranks [0, n)) and reduce the surviving order statistics.
  Both are UNWEIGHTED over the included clients (the Byzantine-robust
  convention of coordinate-wise trimmed mean / median, Yin et al.,
  arXiv:1803.01498) — p_k weighting would let one heavy client dominate
  the order statistics it is supposed to be protected from.
- ``dp`` applies a per-client multiplicative clip scale (computed upstream
  from whole-model L2 norms) inside the weighted sum and adds
  pre-generated Gaussian noise scaled by ``noise_scale / den`` — DP-FedAvg
  (McMahan et al., arXiv:1710.06963) on the renormalized gated mean. The
  noise vector is generated OUTSIDE the kernel with jax.random so the
  Pallas and jnp lowerings are comparable (the in-kernel TPU PRNG would
  diverge from the CPU path).

Every variant returns an EXACT zero vector when no client is included
(zero inclusion mass) — the old 0/1e-30 guard is kept only as a
divide-safety net, never observed. Gated-out rows are masked before the
reduction so a non-finite update from an excluded client cannot leak
through 0 * NaN.

On TPU every aggregator compiles with the identity and int8 wire codecs.
The topk and sketch decoders index into a whole [C, k] / [C, dim]
operand per grid cell (a gather Mosaic does not lower, on an operand far
beyond VMEM at LM width), so they run only in interpret mode and
``fedagg_pallas`` refuses them for the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def order_stat_reduce(u, n, trim_frac=None):
    """Coordinate-wise trimmed mean (``trim_frac`` set) or median (None) of
    the n included rows of a [C, bm] f32 tile whose excluded rows hold
    +inf, reduced over the client axis to [1, bm].

    Ranks instead of a sort: row i's rank in column m counts the rows that
    order before it (smaller value, ties broken by row index), so the ranks
    are a permutation and the included rows take ranks [0, n). C static
    compare passes over the 2-D tile, with no permutes, gathers or 1-D
    vectors, so the same code runs inside the Pallas grid cell (where
    Mosaic accepts only such ops) and as the jnp lowering in
    ``kernels/ops.py``. n == 0 (or no survivor) -> exact zero."""
    C = u.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    rank = jnp.zeros(u.shape, jnp.int32)
    for j in range(C):
        uj = u[j:j + 1, :]
        before = (uj < u) | ((uj == u) & (row > j))
        rank = rank + before.astype(jnp.int32)
    if trim_frac is None:
        lo, hi = (n - 1) // 2, n // 2                          # even n: average
        out = 0.5 * (jnp.sum(jnp.where(rank == lo, u, 0.0), axis=0, keepdims=True)
                     + jnp.sum(jnp.where(rank == hi, u, 0.0), axis=0, keepdims=True))
        return jnp.where(n > 0, out, 0.0)
    t = (jnp.float32(trim_frac) * n.astype(jnp.float32)).astype(jnp.int32)
    keep = (rank >= t) & (rank < n - t)                        # survivors
    cnt = n - 2 * t
    total = jnp.sum(jnp.where(keep, u, 0.0), axis=0, keepdims=True)
    return jnp.where(cnt > 0, total / jnp.maximum(cnt, 1).astype(jnp.float32), 0.0)


def _included_stats(g):
    """Inclusion mask [C, 1] bool and included count n (traced i32 scalar)."""
    inc = g > 0
    return inc, jnp.sum(inc.astype(jnp.float32)).astype(jnp.int32)


# --------------------------------------------------------- wire-codec decode
# Each decoder turns a grid cell's ENCODED operand refs into the decoded
# [C, block_m] f32 tile, entirely in VMEM/registers — the dense buffer is
# never materialized in HBM on this path (the WireCodec contract,
# core/aggregation.py). The aggregator kernels below are codec-agnostic:
# they see only the decoded tile.

def _decode_identity(refs):
    (u_ref,) = refs
    return u_ref[...].astype(jnp.float32)


def _decode_int8(refs):
    # dequantize-in-register: int8 rows times the per-client [C, 1] scale
    u_ref, s_ref = refs
    return u_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)


def _decode_topk(block_m, refs):
    # sparse-scatter-accumulate: every cell walks the k (value, index)
    # pairs once and one-hot-accumulates the entries landing in its
    # column range. Indices within a row are distinct (top_k), so the
    # accumulation places each value exactly once — bit-identical to the
    # jnp lowering's scatter-add.
    v_ref, i_ref = refs                                        # [C, k] each
    v = v_ref[...].astype(jnp.float32)
    ix = i_ref[...]
    C, k = v.shape
    base = pl.program_id(0) * block_m
    cols = base + jax.lax.broadcasted_iota(jnp.int32, (C, block_m), 1)

    def body(j, acc):
        vj = jax.lax.dynamic_slice(v, (0, j), (C, 1))          # [C, 1]
        ij = jax.lax.dynamic_slice(ix, (0, j), (C, 1))         # [C, 1]
        return acc + jnp.where(cols == ij, vj, 0.0)

    return jax.lax.fori_loop(0, k, body, jnp.zeros((C, block_m), jnp.float32))


def _decode_sketch(refs):
    # CountSketch estimate: gather each column's bucket from the [C, dim]
    # sketch rows and apply its sign
    s_ref, h_ref, sg_ref = refs
    s = s_ref[...].astype(jnp.float32)                         # [C, dim]
    h = h_ref[0, :]                                            # [bm] i32
    sg = sg_ref[...].astype(jnp.float32)                       # [1, bm]
    return jnp.take(s, h, axis=1) * sg


def _weighted_mean(wg, scale, u):
    """sum_k wg_k scale_k u_k / sum_k wg_k over [C, 1] columns and a
    [C, bm] tile; gated-out rows (and their scales) are masked first."""
    inc = wg > 0
    den = jnp.sum(wg)
    u = jnp.where(inc, u, 0.0)
    num = jnp.sum(jnp.where(inc, wg * scale, 0.0) * u, axis=0, keepdims=True)
    return num, den


def _mean_kernel(decode, n_enc, *refs):
    w_ref, g_ref, o_ref = refs[n_enc], refs[n_enc + 1], refs[-1]
    wg = (w_ref[...] * g_ref[...]).astype(jnp.float32)        # [C, 1]
    num, den = _weighted_mean(wg, 1.0, decode(refs[:n_enc]))
    out = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def _dp_kernel(noise_scale, decode, n_enc, *refs):
    w_ref, g_ref = refs[n_enc], refs[n_enc + 1]
    s_ref, n_ref, o_ref = refs[n_enc + 2], refs[n_enc + 3], refs[-1]
    wg = (w_ref[...] * g_ref[...]).astype(jnp.float32)        # [C, 1]
    # clip scales are masked on excluded rows: a NaN delta in a gated-out
    # client makes its row_scale NaN and 0 * NaN would leak through
    num, den = _weighted_mean(wg, s_ref[...].astype(jnp.float32),
                              decode(refs[:n_enc]))
    safe = jnp.maximum(den, 1e-30)
    noisy = num / safe + n_ref[...].astype(jnp.float32) * (noise_scale / safe)
    o_ref[...] = jnp.where(den > 0, noisy, 0.0).astype(o_ref.dtype)


def _order_stat_kernel(trim_frac, decode, n_enc, *refs):
    g_ref, o_ref = refs[n_enc + 1], refs[-1]                   # unweighted
    inc, n = _included_stats(g_ref[...])
    u = jnp.where(inc, decode(refs[:n_enc]), jnp.inf)
    o_ref[...] = order_stat_reduce(u, n, trim_frac).astype(o_ref.dtype)


# codecs whose in-kernel decode has a Mosaic lowering (see module docstring)
TPU_CODECS = ("identity", "int8")


def fedagg_pallas(updates, weights, gates, *, block_m=2048, interpret=False,
                  aggregator="mean", trim_frac=0.0, row_scale=None,
                  noise=None, noise_scale=0.0, codec="identity",
                  dequant_scale=None, topk_idx=None, sketch_h=None,
                  sketch_sign=None, out_m=None, out_dtype=None):
    """updates: [C, M] (or the codec's wire shape); weights, gates: [C] -> [M].

    aggregator: mean | trimmed_mean | median | dp — one fused kernel launch
    regardless of variant. ``dp`` additionally takes ``row_scale`` [C]
    (per-client clip factors), ``noise`` [M] (standard-normal draws) and a
    static ``noise_scale`` (sigma numerator = dp_noise * dp_clip; divided
    by the inclusion mass inside the cell). ``cosine_filter`` is a gate
    pre-pass upstream and lands here as plain ``mean``.

    ``codec`` selects the in-kernel wire decode, COMPOSED with every
    aggregator in the same launch (decode feeds the mean/dp sum directly,
    and runs before the order-statistics ranking):

    - ``identity`` — ``updates`` is the dense [C, M] buffer (output in
      ``updates.dtype``).
    - ``int8`` — ``updates`` is [C, M] int8; ``dequant_scale`` [C] f32
      dequantizes each row in-register after the tile load.
    - ``topk`` — ``updates`` is [C, k] f32 values with ``topk_idx``
      [C, k] i32 column indices (both full-array operands per cell);
      ``out_m`` gives the true M. Each cell scatter-accumulates its tile.
      Interpret mode only.
    - ``sketch`` — ``updates`` is [C, dim] f32 CountSketch rows (full per
      cell); ``sketch_h`` / ``sketch_sign`` [M] are the shared hash/sign
      planes (tiled per block); ``out_m`` gives the true M. Interpret
      mode only.

    Codec outputs are f32 (the wire dtype no longer matches the model).
    ``out_dtype`` overrides the output dtype; accumulation is f32 either
    way. The dense decode is never materialized in HBM — each grid cell decodes
    its own [C, block_m] tile in VMEM."""
    if not interpret and codec not in TPU_CODECS:
        raise NotImplementedError(
            f"fedagg_pallas: wire codec {codec!r} has no compiled TPU "
            f"kernel (only {TPU_CODECS} do); its decode gathers from a "
            "whole [C, k]/[C, dim] operand per grid cell. Run it with "
            "interpret=True, or pick a codec from that list")
    C = updates.shape[0]
    M = int(out_m) if out_m is not None else updates.shape[1]
    if out_dtype is None:
        out_dtype = updates.dtype if codec == "identity" else jnp.float32
    # a ragged last tile is a partial block: Pallas clips its out-of-range
    # columns on write, and every reduction here is column-local, so
    # nothing is padded (a padded copy of [C, M_total] would cost a whole
    # extra buffer of device memory at LM width)
    block_m = min(block_m, -(-M // 128) * 128)
    nm = pl.cdiv(M, block_m)

    def col(x):                       # per-client [C] vector -> [C, 1]
        return jnp.reshape(x, (C, 1))

    def row(x):                       # per-column [M] vector -> [1, M]
        return jnp.reshape(x, (1, M))

    vec_spec = pl.BlockSpec((C, 1), lambda im: (0, 0))
    row_spec = pl.BlockSpec((1, block_m), lambda im: (0, im))
    tile_spec = pl.BlockSpec((C, block_m), lambda im: (0, im))

    if codec == "identity":
        enc_specs = [tile_spec]
        enc_ops = [updates]
        decode = _decode_identity
    elif codec == "int8":
        if dequant_scale is None:
            raise ValueError("codec='int8' needs dequant_scale [C]")
        enc_specs = [tile_spec, vec_spec]
        enc_ops = [updates, col(dequant_scale)]
        decode = _decode_int8
    elif codec == "topk":
        if topk_idx is None or out_m is None:
            raise ValueError("codec='topk' needs topk_idx [C, k] and out_m")
        k = updates.shape[1]
        full = pl.BlockSpec((C, k), lambda im: (0, 0))
        enc_specs = [full, full]
        enc_ops = [updates, topk_idx]
        decode = functools.partial(_decode_topk, block_m)
    elif codec == "sketch":
        if sketch_h is None or sketch_sign is None or out_m is None:
            raise ValueError(
                "codec='sketch' needs sketch_h [M], sketch_sign [M], out_m")
        dim = updates.shape[1]
        enc_specs = [pl.BlockSpec((C, dim), lambda im: (0, 0)),
                     row_spec, row_spec]
        enc_ops = [updates, row(sketch_h), row(sketch_sign)]
        decode = _decode_sketch
    else:
        raise ValueError(f"unknown wire codec {codec!r}")

    in_specs = enc_specs + [vec_spec, vec_spec]
    operands = enc_ops + [col(weights), col(gates)]
    n_enc = len(enc_ops)
    if aggregator == "mean":
        kernel = functools.partial(_mean_kernel, decode, n_enc)
    elif aggregator == "trimmed_mean":
        kernel = functools.partial(_order_stat_kernel, float(trim_frac),
                                   decode, n_enc)
    elif aggregator == "median":
        kernel = functools.partial(_order_stat_kernel, None, decode, n_enc)
    elif aggregator == "dp":
        if row_scale is None or noise is None:
            raise ValueError("aggregator='dp' needs row_scale [C] and noise [M]")
        kernel = functools.partial(_dp_kernel, float(noise_scale), decode,
                                   n_enc)
        in_specs += [vec_spec, row_spec]
        operands += [col(row_scale), row(noise)]
    else:
        raise ValueError(f"unknown in-kernel aggregator {aggregator!r}")

    with jax.named_scope("kernel.fedagg"):
        out = pl.pallas_call(
            kernel,
            name="kernel.fedagg",
            grid=(nm,),
            in_specs=in_specs,
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((1, M), out_dtype),
            interpret=interpret,
        )(*operands)
    return out.reshape(M)
