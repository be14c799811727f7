"""jit'd dispatch layer for the Pallas kernels.

Every op has (a) a Pallas TPU kernel (``<name>.py``), (b) a jnp lowering
here (chunked / memory-safe), and (c) a naive oracle in ``ref.py`` used by
tests.

``flash_attention``, ``fedagg`` and ``expert_ffn`` take
``use_pallas=None`` by default, which follows the platform
(``pallas_default``): the compiled Pallas kernels where the default
backend is a TPU, the jnp lowerings elsewhere. ``expert_ffn``'s grouped
matmul (``grouped_matmul``) is the Pallas kernel that ships with JAX
(megablox ``gmm``).
Nothing falls back at run time: on a TPU an unsupported Pallas variant
raises. ``decode_attention`` and ``ssm_scan`` keep the jnp lowering unless
a caller passes ``use_pallas=True``, because their Pallas kernels do not
compile for TPU yet (ROADMAP). On a CPU backend the Pallas kernels run
only with ``interpret=True`` (tests do this explicitly).

XLA cannot partition a Mosaic kernel, so on a multi-device mesh the
caller places the kernels (``kernels_per_shard``): each runs per shard
under ``jax.shard_map``.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def pallas_default() -> bool:
    """True where the compiled Pallas kernels are the default path: the
    default backend is a TPU (the only backend Mosaic compiles for)."""
    return jax.default_backend() == "tpu"


# (mesh, batch_axes, head_axes) while tracing under ``kernels_per_shard``
_SHARDS = contextvars.ContextVar("kernel_shards", default=None)


@contextlib.contextmanager
def kernels_per_shard(mesh, batch_axes=(), head_axes=()):
    """Trace the Pallas kernels inside this block per shard of ``mesh``
    under ``jax.shard_map``. A kernel's batch dim (attention's B, fedagg's
    client rows) splits over ``batch_axes`` and attention heads over
    ``head_axes`` where they divide evenly; every other dim is whole on
    each shard. The caller owns the placement: the pod rounds
    (``fl/sharded.py``) name their data-parallel axes, and a body vmapped
    over clients with ``spmd_axis_name`` names none, since its vmap maps
    the client axis over them. ``mesh=None`` runs the kernels unwrapped."""
    token = _SHARDS.set(None if mesh is None else
                        (mesh, tuple(batch_axes), tuple(head_axes)))
    try:
        yield
    finally:
        _SHARDS.reset(token)


def _split(mesh, axes, n):
    """``axes`` as one spec entry when a dim of size ``n`` splits evenly
    over them, else None (the dim is whole on every shard)."""
    size = math.prod(mesh.shape[a] for a in axes)
    return axes if axes and n % size == 0 else None


# ============================================================ flash attention
def _flash_attention_jnp(q, k, v, *, causal, window, block_kv, kv_len=None,
                         scale=None, mm_dtype=None):
    """Blockwise online-softmax attention (no [S,S] materialization).

    q: [B,Sq,H,hd]; k/v: [B,Skv,KV,hd]; queries occupy the LAST Sq absolute
    positions of the kv sequence (q_offset = Skv - Sq).
    mm_dtype: matmul input dtype (e.g. bf16); softmax state stays f32.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    block = min(block_kv, Skv)
    q_offset = Skv - Sq
    if Skv % block:                       # pad kv to a block multiple, mask the tail
        pad = block - Skv % block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = Skv
        Skv += pad
    nblk = Skv // block

    md = mm_dtype or jnp.float32
    qf = (q.astype(jnp.float32) * scale).astype(md).reshape(B, Sq, KV, G, hd)
    kb = k.astype(md).reshape(B, nblk, block, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.astype(md).reshape(B, nblk, block, KV, hd).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, blk = inp
        k_pos = blk * block + jnp.arange(block)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qf, kc,
                       preferred_element_type=jnp.float32)     # [B,KV,G,Sq,blk]
        mask = jnp.ones((Sq, block), bool)
        if kv_len is not None:
            mask = mask & (k_pos[None, :] < kv_len)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p_, axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bkgqh", p_.astype(md), vc,
                        preferred_element_type=jnp.float32)
        return (m_new, l_new, acc * corr[..., None] + pv), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def _flash_pallas_fits(q, k, kv_len, block=128):
    """The Pallas kernel's domain: a full kv sequence (no ``kv_len``) and
    Sq / Skv that are block multiples or fit one block."""
    return kv_len is None and all(n <= block or n % block == 0
                                  for n in (q.shape[1], k.shape[1]))


def flash_attention(q, k, v, *, causal=True, window=0, block_kv=1024,
                    kv_len=None, scale=None, use_pallas=None, interpret=False,
                    mm_dtype=None):
    """``use_pallas=None`` runs the Pallas kernel on TPU for every call in
    its domain (``_flash_pallas_fits``) and the blockwise jnp lowering for
    the rest (ragged lengths such as whisper's 1500 frames)."""
    if use_pallas is None:
        use_pallas = pallas_default() and _flash_pallas_fits(q, k, kv_len)
    if use_pallas:
        from repro.kernels.flash_attention import flash_attention_pallas
        fn = functools.partial(flash_attention_pallas, causal=causal,
                               window=window, kv_len=kv_len, scale=scale,
                               interpret=interpret)
        shards = _SHARDS.get()
        if shards is not None:
            mesh, batch_axes, head_axes = shards
            spec = P(_split(mesh, batch_axes, q.shape[0]), None,
                     _split(mesh, head_axes, k.shape[2]), None)
            fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec, check_vma=False)
        return fn(q, k, v)
    return _flash_attention_jnp(q, k, v, causal=causal, window=window,
                                block_kv=block_kv, kv_len=kv_len, scale=scale,
                                mm_dtype=mm_dtype)


# ============================================================ grouped matmul
GMM_TM = 256        # rows a tile; the rows are padded to a multiple of it


def _gmm_tile(x):
    """A tile of the contraction or output dim: the whole dim up to 1536,
    else the largest of 512, 256 and 128 that divides it."""
    if x <= 1536:
        return x
    return next((t for t in (512, 256, 128) if x % t == 0), x)


def _gmm_tiling(m, k, n):
    """(m, k, n) tiles of one product; the backward's products look up
    their own shapes here too."""
    return GMM_TM, _gmm_tile(k), _gmm_tile(n)


def _grouped_matmul_jnp(lhs, rhs, group_sizes):
    """Every group's product on every row, each row keeping its own group's
    (g times the work; ``jax.lax.ragged_dot`` cannot be vmapped over the
    spatial round's clients)."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])
    member = (row >= (ends - group_sizes)[:, None]) & (row < ends[:, None])
    every = jnp.einsum("mk,gkn->gmn", lhs, rhs)
    return jnp.sum(jnp.where(member[..., None], every, 0), axis=0)


def grouped_matmul(lhs, rhs, group_sizes, *, use_pallas=None,
                   interpret=False):
    """[m, k] rows in contiguous groups, each times its group's [k, n] of
    ``rhs`` [g, k, n] -> [m, n]: group i is the ``group_sizes[i]`` rows
    after group i-1's, and the rows past the last group come out 0.

    On TPU (``use_pallas=None``) the Pallas grouped matmul that ships with
    JAX (megablox ``gmm``), which visits only the tiles that the groups
    cover; elsewhere every group on every row, masked. The product is one
    shard's: on a multi-device mesh ``expert_ffn`` places it."""
    if use_pallas is None:
        use_pallas = pallas_default()
    if not use_pallas:
        return _grouped_matmul_jnp(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = lhs.shape[0]
    pad = -m % GMM_TM
    group_sizes = group_sizes.astype(jnp.int32)
    # the rows past the groups form one more group, which has no rhs and
    # which the kernel zeroes
    rest = (m + pad - jnp.sum(group_sizes))[None]

    return gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs,
               jnp.concatenate([group_sizes, rest]),
               preferred_element_type=lhs.dtype, tiling=_gmm_tiling,
               interpret=interpret)[:m]


def _expert_ffn_local(xf, tope, topw, w_gate, w_up, w_down, first, mm):
    """``expert_ffn`` on one shard, whose experts are ``first`` onwards:
    f32 [T, d]."""
    T, k = tope.shape
    E = w_gate.shape[0]
    with jax.named_scope("kernel.moe_route"):
        e = tope.reshape(-1) - first                                  # [T*k]
        # the held experts' slots first, one contiguous group each
        e = jnp.where((e >= 0) & (e < E), e, E)
        sizes = jnp.sum(e[:, None] == jnp.arange(E), axis=0, dtype=jnp.int32)
        order = jnp.argsort(e, stable=True)
        xs = jnp.repeat(xf, k, axis=0).at[order].get(unique_indices=True)
    with jax.named_scope("kernel.moe_gmm"):
        h = jax.nn.silu(mm(xs, w_gate, sizes)) * mm(xs, w_up, sizes)
        ys = mm(h, w_down, sizes)                                 # 0 unheld
    with jax.named_scope("kernel.moe_combine"):
        slot = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype), unique_indices=True)
        return jnp.einsum("tkd,tk->td",
                          ys.at[slot].get(unique_indices=True).reshape(
                              T, k, -1), topw,
                          preferred_element_type=jnp.float32)


def expert_ffn(xf, tope, topw, w_gate, w_up, w_down, *, expert_parallel=False,
               use_pallas=None, interpret=False):
    """The held experts' SwiGLU of every routed slot, dropless: token rows
    ``xf`` [T, d], routed to the experts ``tope`` [T, k] (numbered over the
    router's whole width) with the weights ``topw`` [T, k]; ``w_gate``,
    ``w_up`` [E, d, f] and ``w_down`` [E, f, d] hold experts 0..E-1.
    Returns f32 [T, d]: each token's weighted sum over its slots to held
    experts; a slot to any other expert adds nothing.

    The slots are sorted by expert, so that each held expert's rows form
    one group, and one ``grouped_matmul`` a projection computes them all,
    whatever their load. Under ``kernels_per_shard`` each shard runs this
    on its own rows (the token rows split over the batch axes) and its own
    part of the weights: the experts split over the head axes where
    ``expert_parallel`` shards them so, else their hidden dim, each where
    it divides evenly. The shards' partial sums over the head axes add up
    after."""
    if use_pallas is None:
        use_pallas = pallas_default()
    mm = functools.partial(grouped_matmul, use_pallas=use_pallas,
                           interpret=interpret)
    shards = _SHARDS.get()
    if shards is None:
        return _expert_ffn_local(xf, tope, topw, w_gate, w_up, w_down, 0, mm)
    mesh, batch_axes, head_axes = shards
    E, _, f = w_gate.shape
    rows = P(_split(mesh, batch_axes, xf.shape[0]), None)
    if expert_parallel:
        heads = _split(mesh, head_axes, E)
        w_specs = (P(heads, None, None),) * 3
    else:
        heads = _split(mesh, head_axes, f)
        w_specs = (P(None, None, heads), P(None, None, heads),
                   P(None, heads, None))

    def local(xf, tope, topw, w_gate, w_up, w_down):
        first = (jax.lax.axis_index(heads) * w_gate.shape[0]
                 if expert_parallel and heads else 0)
        return _expert_ffn_local(xf, tope, topw, w_gate, w_up, w_down, first,
                                 mm)[None]

    parts = jax.shard_map(local, mesh=mesh, in_specs=(rows,) * 3 + w_specs,
                          out_specs=P(heads, *rows), check_vma=False)(
        xf, tope, topw, w_gate, w_up, w_down)
    return jnp.sum(parts, axis=0)


# ============================================================ decode attention
def _decode_attention_jnp(q, k_cache, v_cache, *, kv_len, scale=None):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k_cache.shape
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k_cache.astype(jnp.float32))
    valid = jnp.arange(Skv)[None, :] < kv_len
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bkgqh", w, v_cache.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, kv_len, scale=None,
                     use_pallas=False, interpret=False):
    if use_pallas:
        from repro.kernels.decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k_cache, v_cache, kv_len=kv_len,
                                       scale=scale, interpret=interpret)
    return _decode_attention_jnp(q, k_cache, v_cache, kv_len=kv_len, scale=scale)


# ===================================================================== fedagg
def _fedagg_jnp(updates, weights, gates):
    wg = (weights * gates).astype(jnp.float32)
    den = jnp.sum(wg)
    u = jnp.where((wg > 0)[:, None], updates.astype(jnp.float32), 0.0)
    num = jnp.einsum("c,cm->m", wg, u)
    out = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)
    return out.astype(updates.dtype)


def _fedagg_dp_jnp(updates, weights, gates, row_scale, noise, noise_scale):
    wg = (weights * gates).astype(jnp.float32)
    den = jnp.sum(wg)
    u = jnp.where((wg > 0)[:, None], updates.astype(jnp.float32), 0.0)
    # mask the clip scales too: an excluded client's NaN delta makes its
    # row_scale NaN, and 0 * NaN would re-poison the masked row
    wgs = jnp.where(wg > 0, wg * row_scale.astype(jnp.float32), 0.0)
    num = jnp.einsum("c,cm->m", wgs, u)
    safe = jnp.maximum(den, 1e-30)
    noisy = num / safe + noise.astype(jnp.float32) * (noise_scale / safe)
    return jnp.where(den > 0, noisy, 0.0).astype(updates.dtype)


def _fedagg_sorted_jnp(updates, gates, *, trim_frac=None):
    """Coordinate-wise trimmed mean (trim_frac set) or median (None) over the
    INCLUDED clients, unweighted — the Byzantine-robust convention (Yin et
    al., arXiv:1803.01498). Excluded clients sort to +inf, so the n included
    values occupy sorted positions [0, n). n == 0 -> exact zero."""
    from repro.kernels.fedagg import order_stat_reduce

    inc = gates > 0
    n = jnp.sum(inc.astype(jnp.int32))
    u = jnp.where(inc[:, None], updates.astype(jnp.float32), jnp.inf)
    # the kernel's own rank-and-select, not jnp.sort: XLA's comparator
    # sort quicksorts every column and dominated whole CPU rounds
    return order_stat_reduce(u, n, trim_frac)[0].astype(updates.dtype)


def _decode_wire_jnp(updates, *, codec, dequant_scale=None, topk_idx=None,
                     sketch_h=None, sketch_sign=None, out_m=None):
    """Decode a wire-codec payload to the dense f32 [C, M] buffer.

    Bit-comparable to the in-kernel decoders in kernels/fedagg.py: int8
    multiplies the per-row scale after the f32 cast; topk scatter-adds the
    (value, index) pairs (indices within a row are distinct, so order is
    irrelevant); sketch gathers each column's CountSketch bucket and
    applies its sign."""
    if codec == "int8":
        if dequant_scale is None:
            raise ValueError("codec='int8' needs dequant_scale [C]")
        return updates.astype(jnp.float32) * dequant_scale.astype(jnp.float32)[:, None]
    if codec == "topk":
        if topk_idx is None or out_m is None:
            raise ValueError("codec='topk' needs topk_idx [C, k] and out_m")
        C = updates.shape[0]
        rows = jnp.arange(C, dtype=jnp.int32)[:, None]
        buf = jnp.zeros((C, int(out_m)), jnp.float32)
        return buf.at[rows, topk_idx].add(updates.astype(jnp.float32))
    if codec == "sketch":
        if sketch_h is None or sketch_sign is None:
            raise ValueError("codec='sketch' needs sketch_h [M] and sketch_sign [M]")
        return (jnp.take(updates.astype(jnp.float32), sketch_h, axis=1)
                * sketch_sign.astype(jnp.float32)[None, :])
    raise ValueError(f"unknown wire codec {codec!r}")


def fedagg(updates, weights, gates, *, use_pallas=None, interpret=False,
           block_m=2048, aggregator="mean", trim_frac=0.0, row_scale=None,
           noise=None, noise_scale=0.0, codec="identity", dequant_scale=None,
           topk_idx=None, sketch_h=None, sketch_sign=None, out_m=None):
    """Gated client aggregation: [C,M],[C],[C] -> [M].

    The fused aggregation path (core/aggregation.py) calls this ONCE per
    round on the whole-model [C, M_total] flattening, so M may be the full
    parameter count; the Pallas kernel tiles M in block_m columns.

    ``aggregator`` selects the in-kernel reduction (mean | trimmed_mean |
    median | dp); all variants return an exact zero vector on a
    zero-inclusion round and mask gated-out rows before reducing. See
    kernels/fedagg.py for the per-variant semantics and extra operands.

    ``codec`` (identity | int8 | topk | sketch) composes the wire decode
    with the reduction: on the Pallas path the decode happens per grid
    cell inside the same launch (no dense decode buffer in HBM); on the
    jnp lowering the buffer is decoded then reduced. Non-identity codecs
    output f32 regardless of the wire dtype; the extra operands
    (``dequant_scale``, ``topk_idx``, ``sketch_h``/``sketch_sign``,
    ``out_m``) are supplied by the codec's encode (core/aggregation.py).

    ``use_pallas=None`` follows the platform (``pallas_default``)."""
    if use_pallas is None:
        use_pallas = pallas_default()
    if use_pallas:
        from repro.kernels.fedagg import fedagg_pallas
        kernel = functools.partial(
            fedagg_pallas, block_m=block_m, interpret=interpret,
            aggregator=aggregator, trim_frac=trim_frac,
            noise_scale=noise_scale, codec=codec, out_m=out_m)
        # operands with a leading client axis, and per-column ones
        rows = {k: x for k, x in dict(
            updates=updates, weights=weights, gates=gates,
            row_scale=row_scale, dequant_scale=dequant_scale,
            topk_idx=topk_idx).items() if x is not None}
        cols = {k: x for k, x in dict(
            noise=noise, sketch_h=sketch_h,
            sketch_sign=sketch_sign).items() if x is not None}
        shards = _SHARDS.get()
        if shards is None:
            return kernel(**rows, **cols)
        mesh, batch_axes, _ = shards
        return _fedagg_on_mesh(
            mesh, batch_axes, kernel, aggregator == "mean", rows, cols,
            updates.dtype if codec == "identity" else jnp.float32)
    if codec != "identity":
        updates = _decode_wire_jnp(updates, codec=codec,
                                   dequant_scale=dequant_scale,
                                   topk_idx=topk_idx, sketch_h=sketch_h,
                                   sketch_sign=sketch_sign, out_m=out_m)
    if aggregator == "mean":
        return _fedagg_jnp(updates, weights, gates)
    if aggregator == "trimmed_mean":
        return _fedagg_sorted_jnp(updates, gates, trim_frac=float(trim_frac))
    if aggregator == "median":
        return _fedagg_sorted_jnp(updates, gates, trim_frac=None)
    if aggregator == "dp":
        if row_scale is None or noise is None:
            raise ValueError("aggregator='dp' needs row_scale [C] and noise [M]")
        return _fedagg_dp_jnp(updates, weights, gates, row_scale, noise,
                              float(noise_scale))
    raise ValueError(f"unknown in-kernel aggregator {aggregator!r}")


def _fedagg_on_mesh(mesh, batch_axes, kernel, linear, rows, cols, out_dtype):
    """The fedagg kernel on a multi-device mesh. The gated mean is linear:
    each shard reduces its own clients (rows split over ``batch_axes``) to
    an f32 partial mean, and one f32 all-reduce of the mass-weighted
    partials (``den_s * out_s``) and masses finishes it; the result is
    cast to ``out_dtype`` once, after the sum, as the single-device
    kernel does. The order statistics and dp need every
    client at once, so they gather the client axis and reduce on every
    shard (the documented client-axis gather of the pod rounds)."""
    split = (_split(mesh, batch_axes, rows["updates"].shape[0])
             if linear else None)

    def body(rows, cols):
        if split is None:
            return kernel(**rows, **cols)
        out = kernel(**rows, **cols, out_dtype=jnp.float32)
        den = jnp.sum((rows["weights"] * rows["gates"]).astype(jnp.float32))
        num = jax.lax.psum(den * out, split)
        den = jax.lax.psum(den, split)
        return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)

    in_specs = (jax.tree.map(lambda _: P(split), rows),
                jax.tree.map(lambda _: P(), cols))
    out = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=P(), check_vma=False)(rows, cols)
    return out.astype(out_dtype)


# ==================================================================== rmsnorm
def _rmsnorm_jnp(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def rmsnorm(x, scale, *, eps=1e-6, use_pallas=False, interpret=False):
    if use_pallas:
        from repro.kernels.rmsnorm import rmsnorm_pallas
        return rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)
    return _rmsnorm_jnp(x, scale, eps)


# =================================================================== ssm scan
def _ssm_scan_jnp(x, dt, A, B, C, D, *, chunk=256):
    """Chunked parallel selective scan (Mamba S6).

    Within a chunk the linear recurrence h_t = a_t h_{t-1} + b_t is solved
    with an associative scan; chunks are chained with a lax.scan carry.
    Shapes as in ref.ssm_scan_ref.
    """
    Bt, S, Di = x.shape
    N = A.shape[1]
    S0 = S
    chunk = min(chunk, S)
    if S % chunk:
        # identity-step padding: dt=0 => a=1, b=0 (state unchanged)
        pad = chunk - S % chunk
        p3 = ((0, 0), (0, pad), (0, 0))
        x, dt, B, C = (jnp.pad(t, p3) for t in (x, dt, B, C))
        S += pad
    nch = S // chunk
    xf = x.astype(jnp.float32).reshape(Bt, nch, chunk, Di).transpose(1, 0, 2, 3)
    dtf = dt.astype(jnp.float32).reshape(Bt, nch, chunk, Di).transpose(1, 0, 2, 3)
    Bf = B.astype(jnp.float32).reshape(Bt, nch, chunk, N).transpose(1, 0, 2, 3)
    Cf = C.astype(jnp.float32).reshape(Bt, nch, chunk, N).transpose(1, 0, 2, 3)
    Af = A.astype(jnp.float32)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    def body(h0, inp):
        xc, dtc, Bc, Cc = inp                              # [Bt,chunk,...]
        a = jnp.exp(dtc[..., None] * Af[None, None])       # [Bt,c,Di,N]
        b = (dtc * xc)[..., None] * Bc[:, :, None, :]      # [Bt,c,Di,N]
        A_cum, B_cum = jax.lax.associative_scan(combine, (a, b), axis=1)
        h = A_cum * h0[:, None] + B_cum                    # [Bt,c,Di,N]
        y = jnp.einsum("bcdn,bcn->bcd", h, Cc)
        return h[:, -1], y

    h0 = jnp.zeros((Bt, Di, N), jnp.float32)
    _, ys = jax.lax.scan(body, h0, (xf, dtf, Bf, Cf))
    y = ys.transpose(1, 0, 2, 3).reshape(Bt, S, Di)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None]
    return y[:, :S0].astype(x.dtype)


def ssm_scan(x, dt, A, B, C, D, *, chunk=256, use_pallas=False, interpret=False):
    if use_pallas:
        from repro.kernels.ssm_scan import ssm_scan_pallas
        return ssm_scan_pallas(x, dt, A, B, C, D, chunk=chunk, interpret=interpret)
    return _ssm_scan_jnp(x, dt, A, B, C, D, chunk=chunk)


def ssm_step(h, xt, dtt, A, Bt_, Ct):
    """Single decode step of the selective scan. h:[B,Di,N] -> (h', y[B,Di])."""
    dA = jnp.exp(dtt[..., None] * A[None].astype(jnp.float32))
    dB = dtt[..., None] * Bt_[:, None, :].astype(jnp.float32)
    h = dA * h + dB * xt[..., None].astype(jnp.float32)
    y = jnp.einsum("bdn,bn->bd", h, Ct.astype(jnp.float32))
    return h, y
