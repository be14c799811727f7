"""FedALIGN renormalized gated aggregation (paper eq. (15)):

    w <- sum_k p_k I_k w_k / sum_k p_k I_k

over client-stacked parameter pytrees. The default ``fused`` path flattens
the WHOLE pytree into one [C, M_total] buffer and invokes the ``fedagg``
kernel (Pallas on TPU, its jnp lowering elsewhere) ONCE per round instead of
once per leaf — one kernel launch, one contraction, and under pjit with the
client axis sharded over (pod, data) exactly one all-reduce: FedALIGN's
entire server-side communication. Accumulation is f32 regardless of leaf
dtype, so fused and per-leaf outputs agree to the cast.

This module also owns three registries:

- the **ServerOptimizer registry**: the fused aggregated delta is a
  pseudo-gradient, and ``aggregate_updates`` applies the configured
  server-side update rule (FedOpt, Reddi et al., arXiv:2003.00295) to it —
  ``sgd`` (FedAvg), ``momentum`` (FedAvgM), ``adam`` (FedAdam), ``yogi``
  (FedYogi) — reusing the update rules from ``optim/optimizers.py``.
  Optimizer moments live in ``fl.engine.FederationState.opt_state`` and
  thread through the round scan.
- the **Aggregator registry** (``FedConfig.aggregator``): how the gated
  client deltas are REDUCED before the server step. ``mean`` is the paper
  rule above; ``trimmed_mean`` / ``median`` are the coordinate-wise
  Byzantine-robust order statistics (Yin et al., arXiv:1803.01498),
  ``dp`` is DP-FedAvg clip+noise (McMahan et al., arXiv:1710.06963), and
  ``cosine_filter`` zeroes the gates of delta-sketch outliers before the
  plain mean. A registered aggregator is a PREPARE function producing
  gate/weight rewrites and in-kernel operands — the reduction itself stays
  one fused fedagg kernel launch per round for every variant.
- the **WireCodec registry** (``FedConfig.wire_codec``): lossy uplink
  compression of the fused [C, M_total] buffer — ``int8`` rows with
  per-client scales, ``topk`` sparsification, ``sketch`` CountSketch
  rows — decoded INSIDE the same fedagg launch (dequantize-in-register /
  sparse-scatter-accumulate / hash-gather per VMEM tile, never a
  materialized dense decode buffer), with per-client error-feedback
  accumulators (``FederationState.ef_accum``) re-injecting the
  compression residual next round so convergence doesn't stall.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import register_validator
from repro.kernels import ops as kops
from repro.optim import optimizers as _opt
from repro.utils import Registry, fold_in_name


def check_client_weights(weights, *, where="client weights"):
    """Validate CONCRETE client weights at the aggregation boundary.

    A negative p_k silently sign-flips that client's contribution (the
    renormalized mean subtracts it); a NaN/inf poisons the whole aggregate.
    Neither is ever a legitimate data fraction, so both fail loudly here.
    Traced values (inside jit) pass through unchecked — jitted callers
    validate at their host-side entry points (fl/simulator, launch/train)
    where the weights are still concrete.
    """
    if isinstance(weights, jax.core.Tracer):
        return weights
    import numpy as np
    w = np.asarray(weights)
    if not np.all(np.isfinite(w)):
        bad = np.flatnonzero(~np.isfinite(w))
        raise ValueError(
            f"{where} must be finite: clients {bad.tolist()} are NaN/inf. "
            "Check the shard spec / data-fraction computation that produced "
            "them — a NaN weight poisons every aggregated parameter.")
    if np.any(w < 0):
        bad = np.flatnonzero(w < 0)
        raise ValueError(
            f"{where} must be non-negative: clients {bad.tolist()} have "
            f"negative weight (min {w.min()}). A negative data fraction "
            "sign-flips that client's update in the renormalized mean; fix "
            "the shard spec instead of aggregating with it.")
    return weights


def flatten_stacked(client_params, dtype=jnp.float32):
    """Client-stacked pytree ([C, ...] leaves) -> one [C, M_total] buffer."""
    leaves = jax.tree.leaves(client_params)
    C = leaves[0].shape[0]
    return jnp.concatenate(
        [leaf.reshape(C, -1).astype(dtype) for leaf in leaves], axis=1)


def aggregate_clients(client_params, weights, gates, *, use_pallas=None,
                      fused=True, interpret=False, aggregator="mean",
                      fed=None, key=None, wire_codec="identity",
                      ef_accum=None):
    """client_params: pytree with leading client axis C on every leaf.

    fused=True (default): one fedagg call on the [C, M_total] flattening;
    fused=False: one fedagg call per leaf (the pre-fusion path, kept as the
    parity reference and for incremental/per-leaf sharded layouts).

    ``aggregator`` names a registered Aggregator (mean | trimmed_mean |
    median | dp | cosine_filter). Non-mean aggregators read their knobs off
    ``fed`` and interpret the client rows as DELTAS (clip norms, outlier
    cosines); ``dp`` additionally needs a PRNG ``key`` for its per-round
    noise draw. Whatever the variant, the reduction stays one fedagg call
    (fused) or one per leaf — the robust work happens inside the kernel,
    plus an O(C * sketch_dim) gate pre-pass for cosine_filter.

    ``wire_codec`` names a registered WireCodec compressing the fused
    buffer's uplink (identity | int8 | topk | sketch); non-identity codecs
    require ``fused=True`` and ``fed=``. With ``ef_accum`` (a pytree of
    f32 per-client error-feedback rows, params-shaped leaves with the same
    leading client axis as ``client_params``) the accumulator is added to
    the rows BEFORE encoding and the call returns ``(aggregate,
    new_ef_accum)`` where ``new_ef_accum`` carries the per-row compression
    residual x - decode(encode(x)) for every transmitting (gate > 0,
    finite-residual) row and the previous accumulator for the rest —
    EF-style memory, so compression bias is re-injected next round instead
    of lost. The identity codec ignores both knobs and keeps the exact
    legacy trace."""
    check_client_weights(weights)
    leaves, treedef = jax.tree.flatten(client_params)
    if not leaves:
        return client_params
    C = leaves[0].shape[0]
    # which rows TRANSMITTED this round — captured before any server-side
    # gate rewrite (cosine_filter): a filtered-out client still encoded and
    # sent its delta, so its EF accumulator must still advance
    tx_gates = gates

    name = resolve_aggregator(aggregator)
    if name != "mean":
        if fed is None:
            raise ValueError(
                f"aggregator={name!r} reads its knobs (trim_frac/dp_clip/"
                "dp_noise/outlier_cos/sketch_dim) off a FedConfig: pass fed=")
        weights, gates, kernel_kw, noise = get_aggregator(name)(
            fed, client_params, weights, gates, key)
    else:
        kernel_kw, noise = {}, None

    codec_name = resolve_wire_codec(wire_codec)
    if codec_name != "identity":
        if fed is None:
            raise ValueError(
                f"wire_codec={codec_name!r} reads its rate knobs "
                "(codec_topk_frac/codec_sketch_dim) off a FedConfig: "
                "pass fed=")
        if not fused:
            raise ValueError(
                f"wire_codec={codec_name!r} compresses the fused "
                "[C, M_total] buffer; call with fused=True")
        return _aggregate_coded(
            codec_name, leaves, treedef, client_params, weights, gates,
            tx_gates, kernel_kw, noise, fed=fed, use_pallas=use_pallas,
            interpret=interpret, ef_accum=ef_accum)
    if ef_accum is not None:
        raise ValueError(
            "ef_accum (error-feedback rows) only makes sense with a "
            "non-identity wire_codec: the identity wire is lossless, its "
            "residual is exactly zero")

    if not fused:
        # per-leaf path: the dp noise vector is ONE [M_total] draw sliced at
        # each leaf's offset, so per-leaf == fused bit-for-bit per coordinate
        sizes = [leaf.size // C for leaf in leaves]
        offs, off = [], 0
        for size in sizes:
            offs.append(off)
            off += size
        agg_leaves = []
        for leaf, size, off in zip(leaves, sizes, offs):
            kw = dict(kernel_kw)
            if noise is not None:
                kw["noise"] = noise[off:off + size]
            out = kops.fedagg(leaf.reshape(C, -1), weights, gates,
                              use_pallas=use_pallas, interpret=interpret, **kw)
            agg_leaves.append(out.reshape(leaf.shape[1:]))
        return jax.tree.unflatten(treedef, agg_leaves)

    # keep a uniform leaf dtype on the wire (bf16 deltas stay bf16 in the
    # [C, M_total] buffer and its collective); mixed-dtype trees go f32.
    # fedagg accumulates in f32 either way, so fused == per-leaf numerics.
    dtypes = {leaf.dtype for leaf in leaves}
    buf_dtype = dtypes.pop() if len(dtypes) == 1 else jnp.float32
    sizes = [leaf.size // C for leaf in leaves]
    buf = flatten_stacked(client_params, dtype=buf_dtype)
    out = kops.fedagg(buf, weights, gates, use_pallas=use_pallas,
                      interpret=interpret, noise=noise, **kernel_kw)
    agg_leaves, off = [], 0
    for leaf, size in zip(leaves, sizes):
        agg_leaves.append(
            out[off:off + size].reshape(leaf.shape[1:]).astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(treedef, agg_leaves)


def _aggregate_coded(codec_name, leaves, treedef, client_params, weights,
                     gates, tx_gates, kernel_kw, noise, *, fed, use_pallas,
                     interpret, ef_accum):
    """The compressed-uplink fused path: encode the f32 [C, M_total] buffer
    (error-feedback rows folded in first), decode-and-reduce inside the one
    fedagg kernel launch, and advance the EF accumulator.

    The dense decode is materialized ONLY for the EF residual (it is the
    definition of the residual); the kernel itself consumes the encoded
    operands and decodes per [C, block_m] tile in VMEM."""
    C = leaves[0].shape[0]
    sizes = [leaf.size // C for leaf in leaves]
    codec = get_wire_codec(codec_name)
    buf = flatten_stacked(client_params, dtype=jnp.float32)
    if ef_accum is not None:
        buf = buf + flatten_stacked(ef_accum, dtype=jnp.float32)
    M = buf.shape[1]
    updates, codec_kw = codec.encode(fed, buf)
    out = kops.fedagg(updates, weights, gates, use_pallas=use_pallas,
                      interpret=interpret, noise=noise, **codec_kw,
                      **kernel_kw)
    agg_leaves, off = [], 0
    for leaf, size in zip(leaves, sizes):
        agg_leaves.append(
            out[off:off + size].reshape(leaf.shape[1:]).astype(leaf.dtype))
        off += size
    agg = jax.tree.unflatten(treedef, agg_leaves)
    if ef_accum is None:
        return agg
    resid = buf - codec.decode(fed, updates, codec_kw, M)
    # rows advance only when they transmitted (gate > 0 BEFORE server-side
    # rewrites) AND the residual is finite — a corrupted (NaN) delta must
    # not poison the accumulator for every later round
    ok = (tx_gates > 0) & jnp.all(jnp.isfinite(resid), axis=1)
    ef_leaves, ef_treedef = jax.tree.flatten(ef_accum)
    new_ef, off = [], 0
    for old, size in zip(ef_leaves, sizes):
        r = resid[:, off:off + size].reshape(old.shape)
        okb = ok.reshape((C,) + (1,) * (old.ndim - 1))
        new_ef.append(jnp.where(okb, r, old.astype(jnp.float32)))
        off += size
    return agg, jax.tree.unflatten(ef_treedef, new_ef)


# ================================================================ aggregators
AGGREGATORS = Registry("aggregator", aliases={None: "mean", "none": "mean"})


def register_aggregator(name: str, *, needs_key=False, in_kernel=True):
    """Register a client-delta Aggregator under ``name``.

    The registered callable is a PREPARE step
    ``prepare(fed, client_deltas, weights, gates, key)
        -> (weights, gates, kernel_kw, noise)``
    run once per round before the fused fedagg call: it may rewrite the
    weight/gate vectors (cosine_filter), attach extra in-kernel operands
    (dp's per-client clip scales), and return a [M_total] noise vector that
    the fused/per-leaf dispatcher slices per leaf. ``kernel_kw`` is passed
    straight to ``kernels.ops.fedagg`` — the reduction itself runs inside
    the kernel (``in_kernel`` aggregators add zero extra HBM passes over
    the [C, M_total] buffer). ``needs_key=True`` marks stochastic
    aggregators: the round loop derives a per-round key
    (``aggregator_key``) only for those, so deterministic traces are
    untouched."""
    return AGGREGATORS.register(name, agg_name=name, needs_key=needs_key,
                                in_kernel=in_kernel)


def resolve_aggregator(name) -> str:
    """Canonical registry name ('none' / None is the plain gated mean)."""
    return AGGREGATORS.resolve(name)


def get_aggregator(name: str) -> Callable:
    return AGGREGATORS.lookup(name)


def aggregator_key(fed, round_idx):
    """Per-round PRNG key for stochastic aggregators (dp's noise draw).

    Derived from ``fed.seed`` via ``fold_in_name`` (crc32 — deterministic
    across processes) + the round index, and computed IDENTICALLY by the
    engine round and both sharded pod rounds, so every backend draws the
    same noise and stays bit-comparable."""
    base = fold_in_name(jax.random.PRNGKey(fed.seed), "aggregator_noise")
    return jax.random.fold_in(base, round_idx)


def inclusion_mass(fed, weights, gates):
    """The configured aggregator's denominator mass for a round — the
    aggregate can be nonzero iff this is > 0 (the zero-inclusion
    ServerOptimizer skip keys off it). mean/dp/cosine_filter renormalize
    by sum p_k I_k; trimmed_mean/median are unweighted order statistics
    over the included clients, so their mass is the included COUNT (a
    zero-weight included client still moves the median)."""
    name = resolve_aggregator(getattr(fed, "aggregator", "mean"))
    if name in ("trimmed_mean", "median"):
        return jnp.sum((gates > 0).astype(jnp.float32))
    return jnp.sum(weights.astype(jnp.float32) * gates.astype(jnp.float32))


@register_validator("aggregator")
def check_aggregator_config(fed):
    """Validate the aggregator knobs whose bad values would corrupt the
    aggregate silently (like check_async_config for the async knobs).
    Registered as the ``validate_config`` "aggregator" hook; direct calls
    are deprecated — call ``repro.configs.base.validate_config(fed)``."""
    name = resolve_aggregator(fed.aggregator)
    get_aggregator(name)
    if name == "trimmed_mean" and not 0.0 <= fed.trim_frac < 0.5:
        raise ValueError(
            f"FedConfig.trim_frac={fed.trim_frac} outside [0, 0.5): trimming "
            "half or more from each side leaves no survivors for any n")
    if name == "dp":
        if fed.dp_clip <= 0:
            raise ValueError(
                f"FedConfig.dp_clip={fed.dp_clip} must be > 0: the clip bound "
                "is the DP sensitivity; 0 would zero every client delta")
        if fed.dp_noise < 0:
            raise ValueError(
                f"FedConfig.dp_noise={fed.dp_noise} must be >= 0 "
                "(noise multiplier z; 0 = clip-only)")
    if name == "cosine_filter":
        if not -1.0 <= fed.outlier_cos <= 1.0:
            raise ValueError(
                f"FedConfig.outlier_cos={fed.outlier_cos} outside [-1, 1]: "
                "it is compared against cosine similarities")
        if fed.sketch_dim <= 0:
            raise ValueError(
                "cosine_filter scores clients on sketch_dim CountSketches; "
                f"FedConfig.sketch_dim={fed.sketch_dim} must be > 0")


def _delta_sq_norms(client_deltas):
    """Per-client squared L2 norm over the WHOLE delta pytree -> [C] f32."""
    leaves = jax.tree.leaves(client_deltas)
    C = leaves[0].shape[0]
    tot = jnp.zeros((C,), jnp.float32)
    for leaf in leaves:
        x = leaf.reshape(C, -1).astype(jnp.float32)
        tot = tot + jnp.sum(x * x, axis=1)
    return tot


@register_aggregator("mean")
def _agg_mean(fed, client_deltas, weights, gates, key):
    # the paper's renormalized gated weighted mean — the kernel default
    return weights, gates, {}, None


@register_aggregator("trimmed_mean")
def _agg_trimmed(fed, client_deltas, weights, gates, key):
    return weights, gates, dict(aggregator="trimmed_mean",
                                trim_frac=float(fed.trim_frac)), None


@register_aggregator("median")
def _agg_median(fed, client_deltas, weights, gates, key):
    return weights, gates, dict(aggregator="median"), None


@register_aggregator("dp", needs_key=True)
def _agg_dp(fed, client_deltas, weights, gates, key):
    """DP-FedAvg: clip each client delta to L2 <= dp_clip (a per-client
    multiplicative factor folded into the kernel's weighted contraction),
    add N(0, (dp_noise * dp_clip / inclusion_mass)^2) per coordinate.

    The noise is drawn OUTSIDE the kernel (one [M_total] jax.random draw
    per round) so the Pallas kernel and the jnp lowering see the very same
    vector — the in-kernel TPU PRNG would break CPU/TPU parity. dp_noise
    is the raw noise multiplier z; ``dp_epsilon`` below composes the
    per-round mechanisms over a run into an (epsilon, delta) report."""
    if key is None:
        raise ValueError(
            "aggregator='dp' draws per-round Gaussian noise and needs the "
            "round key: thread key=aggregator_key(fed, round_idx) through "
            "aggregate_clients/aggregate_delta")
    norms = jnp.sqrt(_delta_sq_norms(client_deltas))
    row_scale = jnp.minimum(1.0, fed.dp_clip / jnp.maximum(norms, 1e-12))
    M = sum(leaf.size for leaf in jax.tree.leaves(client_deltas))
    C = jax.tree.leaves(client_deltas)[0].shape[0]
    noise = jax.random.normal(key, (M // C,), jnp.float32)
    kw = dict(aggregator="dp", row_scale=row_scale,
              noise_scale=float(fed.dp_noise) * float(fed.dp_clip))
    return weights, gates, kw, noise


# ============================================================ DP accounting
# RDP orders to minimize over: dense where the optimum usually lands for
# z in [0.3, 10] over 1..1e5 rounds, sparse log-spaced tail for tiny z.
DP_RDP_ORDERS = tuple([1.25, 1.5, 1.75, 2.0, 2.5, 3.0, 4.0, 5.0, 6.0, 8.0,
                       10.0, 12.0, 16.0, 20.0, 24.0, 32.0, 48.0, 64.0,
                       96.0, 128.0, 192.0, 256.0, 384.0, 512.0])


def dp_epsilon(noise_multiplier: float, steps: int, delta: float,
               orders=DP_RDP_ORDERS):
    """(epsilon, best_order) for ``steps`` compositions of the Gaussian
    mechanism with noise multiplier z (= FedConfig.dp_noise), at the given
    target ``delta`` — the budget the ``dp`` aggregator actually spends.

    Renyi DP of one Gaussian mechanism at order alpha is alpha / (2 z^2)
    (Mironov 2017, arXiv:1702.07476 Prop. 7); RDP composes additively over
    rounds, and converts to (eps, delta)-DP via
    eps = min_alpha [ steps * alpha / (2 z^2) + log(1/delta) / (alpha - 1) ]
    (ibid. Prop. 3). This is the standard moments-accountant bound for
    full-batch participation (no subsampling amplification — every gated
    client contributes each round, which is FedALIGN's regime); it is
    conservative when participation sampling thins cohorts.

    z <= 0 means no noise: epsilon is infinite. Sanity anchor: z=1, one
    step, delta=1e-5 -> eps ~ 5.3."""
    if steps <= 0:
        return 0.0, None
    if noise_multiplier <= 0:
        return float("inf"), None
    if not (0.0 < delta < 1.0):
        raise ValueError(f"dp_epsilon needs a target delta in (0, 1), "
                         f"got {delta}")
    z2 = float(noise_multiplier) ** 2
    log1d = math.log(1.0 / float(delta))
    best, best_order = float("inf"), None
    for a in orders:
        if a <= 1.0:
            continue
        eps = steps * a / (2.0 * z2) + log1d / (a - 1.0)
        if eps < best:
            best, best_order = eps, a
    return best, best_order


def dp_report(fed, rounds: int):
    """(epsilon, delta) actually spent by a run of ``rounds`` rounds under
    this config, or None when the run is not differentially private
    (aggregator != 'dp', or clip-only dp_noise=0)."""
    if resolve_aggregator(getattr(fed, "aggregator", "mean")) != "dp":
        return None
    if fed.dp_noise <= 0:
        return None
    eps, _ = dp_epsilon(float(fed.dp_noise), int(rounds), float(fed.dp_delta))
    return eps, float(fed.dp_delta)


@register_aggregator("cosine_filter", in_kernel=False)
def _agg_cosine(fed, client_deltas, weights, gates, key):
    """Zero the gate of clients whose delta DIRECTION disagrees with the
    cohort: cosines are estimated on sketch_dim CountSketches (one O(M)
    pass per client, reusing engine.delta_sketch), so the similarity pass
    is O(C * sketch_dim) — never [C, C] on full deltas. The reference is
    the gated weighted mean of the per-client NORMALIZED sketches (the
    mean direction): normalizing first means a norm-boosted Byzantine
    client cannot buy reference mass, which a raw-delta mean would grant
    it. Clients with cos < fed.outlier_cos are dropped for the round; the
    reduction then proceeds as the plain gated mean (same single kernel
    launch, this is purely a gate rewrite)."""
    from repro.fl.engine import delta_sketch
    skey = fold_in_name(jax.random.PRNGKey(fed.seed), "aggregator_cosine_sketch")
    sk = jax.vmap(lambda d: delta_sketch(d, skey, fed.sketch_dim))(client_deltas)
    norms = jnp.sqrt(jnp.sum(sk * sk, axis=1))
    dirs = sk / jnp.maximum(norms, 1e-12)[:, None]
    wg = (weights * gates).astype(jnp.float32)
    # mask excluded rows before the weighted mean: a non-finite delta
    # behind gate 0 sketches to NaN and 0 * NaN would poison the reference
    ref = (jnp.einsum("c,cd->d", wg, jnp.where((wg > 0)[:, None], dirs, 0.0))
           / jnp.maximum(jnp.sum(wg), 1e-30))
    ref = ref / jnp.maximum(jnp.sqrt(jnp.sum(ref * ref)), 1e-12)
    cos = dirs @ ref
    keep = (cos >= fed.outlier_cos).astype(gates.dtype)
    return weights, gates * keep, {}, None


# ============================================================== wire codecs
WIRE_CODECS = Registry(
    "wire codec", aliases={None: "identity", "": "identity",
                           "none": "identity"})


def register_wire_codec(name: str):
    """Register a WireCodec under ``name`` (decorator, like
    ``register_aggregator``).

    A WireCodec is lossy uplink compression of the fused [C, M_total]
    client-delta buffer — the client -> server stream that dominates
    federated communication at pod scale. The registered object provides
    three static methods:

    - ``encode(fed, buf) -> (updates, codec_kw)``: compress the f32
      [C, M] buffer into the wire operand ``updates`` (whatever the codec
      transmits — int8 rows, [C, k] top-k values, [C, dim] sketch rows)
      plus the extra operands/kwargs ``codec_kw`` that
      ``kernels.ops.fedagg`` needs to decode-and-reduce INSIDE the one
      fused kernel launch (per-client dequant scales, index planes,
      hash/sign streams, and the true output length ``out_m``).
    - ``decode(fed, updates, codec_kw, M) -> [C, M] f32``: the dense
      decode — used ONLY for the error-feedback residual and by tests.
      The aggregation itself never materializes it: the kernel decodes
      per [C, block_m] tile in VMEM (dequantize-in-register, sparse
      scatter-accumulate, sketch gather).
    - ``wire_bytes(fed, C, M) -> int``: analytic uplink bytes per round
      (the bench's ``bytes_per_round`` metric).
    """
    return WIRE_CODECS.register(name, codec_name=name)


def resolve_wire_codec(name) -> str:
    """Canonical registry name ('none' / None / '' mean identity)."""
    return WIRE_CODECS.resolve(name)


def get_wire_codec(name):
    return WIRE_CODECS.lookup(name)


@register_validator("codec")
def check_codec_config(fed):
    """Validate the wire-codec knobs whose bad values would corrupt the
    uplink silently (same contract as ``check_aggregator_config``:
    actionable errors at the engine boundary, no-op when disabled).
    Registered as the ``validate_config`` "codec" hook; direct calls are
    deprecated."""
    name = resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
    get_wire_codec(name)
    if name == "identity":
        return
    if not fed.fused_agg:
        raise ValueError(
            f"wire_codec={name!r} compresses the fused [C, M_total] buffer; "
            "fused_agg=False never builds that buffer (one kernel call per "
            "leaf) — enable fused_agg or set wire_codec='identity'")
    if name == "topk" and not 0.0 < float(fed.codec_topk_frac) <= 1.0:
        raise ValueError(
            f"FedConfig.codec_topk_frac={fed.codec_topk_frac} outside "
            "(0, 1]: it is the kept fraction of M_total per client row "
            "(k = max(1, floor(frac * M)))")
    if name == "sketch" and int(fed.codec_sketch_dim) < 1:
        raise ValueError(
            f"FedConfig.codec_sketch_dim={fed.codec_sketch_dim} must be "
            ">= 1 (the CountSketch row width on the wire)")


def wire_sketch_streams(fed, M: int):
    """The run-constant CountSketch hash/sign planes of the sketch codec:
    ``h`` [M] i32 buckets, ``sign`` [M] f32 Rademacher signs.

    One named stream off the config seed (``fold_in_name`` — crc32, so
    deterministic across processes), SHARED by every client and every
    round: encode buckets coordinates with ``h``/``sign``, decode gathers
    the same buckets back, and sketched rounds stay backend-identical."""
    dim = int(fed.codec_sketch_dim)
    key = fold_in_name(jax.random.PRNGKey(fed.seed), "wire_sketch")
    kh, ks = jax.random.split(key)
    h = jax.random.randint(kh, (M,), 0, dim, dtype=jnp.int32)
    sign = jax.random.rademacher(ks, (M,), dtype=jnp.float32)
    return h, sign


def wire_bytes_per_round(fed, num_rows: int, m_total: int) -> int:
    """Analytic uplink bytes for one round: ``num_rows`` client rows (C
    dense, K under a cohort gather) of ``m_total`` coordinates through the
    configured ``fed.wire_codec`` (identity pays ``agg_dtype`` bytes)."""
    codec = get_wire_codec(getattr(fed, "wire_codec", "identity"))
    return int(codec.wire_bytes(fed, int(num_rows), int(m_total)))


@register_wire_codec("identity")
class _IdentityCodec:
    """No codec: the [C, M] buffer travels as-is at ``fed.agg_dtype``."""

    @staticmethod
    def encode(fed, buf):
        return buf, {}

    @staticmethod
    def decode(fed, updates, codec_kw, M):
        return updates.astype(jnp.float32)

    @staticmethod
    def wire_bytes(fed, C, M):
        return C * M * jnp.dtype(fed.agg_dtype).itemsize


@register_wire_codec("int8")
class _Int8Codec:
    """Symmetric per-client-row int8: q = round(x / scale) clipped to
    [-127, 127], scale = rowmax|x| / 127 (1.0 on an all-zero row, so its
    decode is exact zero). The wire is [C, M] int8 plus one f32 scale per
    client — 4x under f32 agg_dtype — and the kernel dequantizes
    ``q * scale`` in-register right after the tile load, under every
    registered aggregator (inside the mean/dp contraction; before the
    order-statistics sort)."""

    @staticmethod
    def encode(fed, buf):
        amax = jnp.max(jnp.abs(buf), axis=1)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(buf / scale[:, None]), -127.0, 127.0)
        return q.astype(jnp.int8), dict(codec="int8", dequant_scale=scale)

    @staticmethod
    def decode(fed, updates, codec_kw, M):
        scale = codec_kw["dequant_scale"].astype(jnp.float32)
        return updates.astype(jnp.float32) * scale[:, None]

    @staticmethod
    def wire_bytes(fed, C, M):
        return C * M + C * 4                        # int8 rows + f32 scales


@register_wire_codec("topk")
class _TopkCodec:
    """Per-client magnitude top-k sparsification: keep the
    k = max(1, floor(codec_topk_frac * M)) largest-|x| coordinates per row
    (an f32 value + i32 index pair each on the wire). The kernel rebuilds
    every [C, block_m] tile with a fori_loop scatter-accumulate over the k
    entries — sparse in HBM, dense only in VMEM."""

    @staticmethod
    def _k(fed, M):
        return max(1, min(int(M), int(float(fed.codec_topk_frac) * M)))

    @staticmethod
    def encode(fed, buf):
        M = buf.shape[1]
        k = _TopkCodec._k(fed, M)
        _, idx = jax.lax.top_k(jnp.abs(buf), k)
        idx = idx.astype(jnp.int32)
        vals = jnp.take_along_axis(buf, idx, axis=1).astype(jnp.float32)
        return vals, dict(codec="topk", topk_idx=idx, out_m=M)

    @staticmethod
    def decode(fed, updates, codec_kw, M):
        C = updates.shape[0]
        rows = jnp.arange(C)[:, None]
        dense = jnp.zeros((C, M), jnp.float32)
        return dense.at[rows, codec_kw["topk_idx"]].add(
            updates.astype(jnp.float32))

    @staticmethod
    def wire_bytes(fed, C, M):
        return C * _TopkCodec._k(fed, M) * 8        # f32 value + i32 index


@register_wire_codec("sketch")
class _SketchCodec:
    """CountSketch uplink (the ``engine.delta_sketch`` projection with ONE
    shared hash/sign stream per run — ``wire_sketch_streams``): each client
    transmits [codec_sketch_dim] f32 bucket sums; decode gathers the
    unbiased estimate ``sign[m] * s[c, h[m]]`` per kernel tile."""

    @staticmethod
    def encode(fed, buf):
        M = buf.shape[1]
        dim = int(fed.codec_sketch_dim)
        h, sign = wire_sketch_streams(fed, M)
        s = jax.vmap(
            lambda row: jax.ops.segment_sum(sign * row, h, num_segments=dim)
        )(buf.astype(jnp.float32))
        return s, dict(codec="sketch", sketch_h=h, sketch_sign=sign, out_m=M)

    @staticmethod
    def decode(fed, updates, codec_kw, M):
        h = codec_kw["sketch_h"]
        sign = codec_kw["sketch_sign"].astype(jnp.float32)
        return updates.astype(jnp.float32)[:, h] * sign[None, :]

    @staticmethod
    def wire_bytes(fed, C, M):
        return C * int(fed.codec_sketch_dim) * 4    # f32 bucket rows


# ========================================================= server optimizers
SERVER_OPTIMIZERS = Registry("server optimizer",
                             aliases={None: "sgd", "none": "sgd"})


def register_server_optimizer(name: str):
    """Register ``factory(fed) -> optim.optimizers.Optimizer`` under ``name``.

    The factory reads its hyper-parameters off the FedConfig (duck-typed:
    anything with the ``server_*`` attributes works); the resulting
    Optimizer's ``init(params)`` builds the moment pytree carried in
    ``FederationState.opt_state`` and ``update`` consumes the aggregated
    delta as a pseudo-gradient."""
    return SERVER_OPTIMIZERS.register(name, opt_name=name)


def resolve_server_opt(name) -> str:
    """Canonical registry name ('none', the legacy no-op, is plain sgd)."""
    return SERVER_OPTIMIZERS.resolve(name)


def get_server_optimizer(name: str) -> Callable:
    return SERVER_OPTIMIZERS.lookup(name)


def server_optimizer(fed):
    """The configured ServerOptimizer instance for ``fed.server_opt``."""
    return get_server_optimizer(fed.server_opt)(fed)


@register_server_optimizer("sgd")
def _server_sgd(fed):
    # w <- w + server_lr * agg_delta: FedAvg at server_lr=1 (the paper rule)
    return _opt.sgd(0.0)


@register_server_optimizer("momentum")
def _server_momentum(fed):
    # FedAvgM: momentum over aggregated deltas
    return _opt.sgd(momentum=fed.server_momentum)


@register_server_optimizer("adam")
def _server_adam(fed):
    return _opt.adam(fed.server_b1, fed.server_b2, fed.server_eps)


@register_server_optimizer("yogi")
def _server_yogi(fed):
    return _opt.yogi(fed.server_b1, fed.server_b2, fed.server_eps)


def apply_server_opt(fed, global_params, opt_state, agg_delta, *, scale=1.0):
    """One server-optimizer step on an already-aggregated global delta.

    Returns (new_params, new_opt_state). The delta enters the optimizer as
    the pseudo-gradient g = -agg_delta, so ``sgd`` at server_lr recovers
    w + server_lr * delta exactly and ``momentum`` reproduces the legacy
    FedAvgM recursion m <- beta m + delta, w <- w + server_lr m.

    ``scale`` pre-multiplies the delta (in f32, after the wire-dtype cast):
    the staleness discount of the ``scan_async`` backend enters the
    optimizer here — one call PER POPPED in-flight slot, each with that
    slot's own scale (the constant ``staleness_decay ** async_depth``
    under the fifo pipe; ``staleness_decay ** age``, optionally times the
    measured-drift cosine, under the variable-lag ``ready`` buffer) — so a
    stale delta's momentum/second-moment contribution is discounted too,
    not just its parameter step. ``scale`` may be a traced scalar (the
    measured-age discounts are); only the python-literal 1.0 skips the
    multiply entirely — the synchronous path is untouched."""
    opt = server_optimizer(fed)
    if isinstance(scale, (int, float)) and float(scale) == 1.0:
        grads = jax.tree.map(lambda d: -d.astype(jnp.float32), agg_delta)
    else:
        grads = jax.tree.map(lambda d: -d.astype(jnp.float32) * scale,
                             agg_delta)
    return opt.update(grads, opt_state, global_params, fed.server_lr)


def aggregate_delta(global_params, client_params, weights, gates, *,
                    fed, interpret=False, key=None, ef_accum=None):
    """Delta-form gated aggregation WITHOUT the server step:

        d <- agg(cast(w_k - w, fed.agg_dtype))      (ONE fused fedagg call)

    Returns the aggregated global delta (leaves in ``fed.agg_dtype``),
    reduced by the configured ``fed.aggregator`` (``key`` feeds stochastic
    aggregators — pass ``aggregator_key(fed, round_idx)`` when
    ``get_aggregator(fed.aggregator).needs_key``). This is the seam the
    ``scan_async`` backend buffers: an in-flight cohort is exactly one of
    these deltas awaiting its (staleness-discounted) ``apply_server_opt``
    some rounds later — the robust/private reduction happens at PUSH time,
    so every aggregator commutes with the async buffer. ``client_params``
    may live in cohort space [K, ...] (zero gates drop padding slots).

    A non-identity ``fed.wire_codec`` compresses the fused buffer's uplink
    before the kernel decodes-and-reduces it; with ``ef_accum`` (the
    per-client error-feedback rows, matching ``client_params``'s leading
    axis) the call returns ``(delta, new_ef_accum)`` — under scan_async
    this runs at PUSH time, so the accumulator advances when the delta is
    encoded, not when it lands. ``wire_codec='identity'`` keeps the exact
    legacy trace (python-level branch, codec code untouched)."""
    ad = jnp.dtype(fed.agg_dtype)
    deltas = jax.tree.map(lambda ck, g: (ck - g[None]).astype(ad),
                          client_params, global_params)
    codec_name = resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
    if codec_name != "identity":
        return aggregate_clients(deltas, weights, gates,
                                 fused=fed.fused_agg, interpret=interpret,
                                 aggregator=getattr(fed, "aggregator", "mean"),
                                 fed=fed, key=key, wire_codec=codec_name,
                                 ef_accum=ef_accum)
    if ef_accum is not None:
        raise ValueError(
            "ef_accum given but fed.wire_codec='identity': the lossless "
            "wire has no compression residual to accumulate")
    return aggregate_clients(deltas, weights, gates,
                             fused=fed.fused_agg, interpret=interpret,
                             aggregator=getattr(fed, "aggregator", "mean"),
                             fed=fed, key=key)


def aggregate_updates(global_params, client_params, weights, gates, *,
                      fed, opt_state=(), interpret=False, key=None):
    """Delta-form gated aggregation + the configured server optimizer:

        d  <- aggregate_delta(...)                  (ONE fused fedagg call)
        w, moments <- ServerOptimizer(fed.server_opt)(w, moments, d)

    Returns (new_params, new_opt_state). ``fed.agg_dtype`` selects the
    reduced-precision delta wire format; accumulation is f32 either way."""
    agg = aggregate_delta(global_params, client_params, weights, gates,
                          fed=fed, interpret=interpret, key=key)
    return apply_server_opt(fed, global_params, opt_state, agg)
