"""Unified federation round engine: pluggable client selection + execution
backends. The single implementation of FedALIGN's gating, eps schedule,
warm-up, and participation sampling — `core/round.py` (simulator) and
`fl/sharded.py` (pjit pod-scale rounds) are thin adapters over this module.

Two orthogonal seams:

* **SelectionStrategy** — who joins the aggregation this round. Decorator-
  registered (`@register_strategy`); a strategy maps a `SelectionContext`
  to a [C] {0,1} inclusion vector for *non-priority* clients (priority
  clients are always in, warm-up and participation are applied uniformly
  by `compute_gates`). Shipped strategies:

    fedalign      — paper rule (§3.1): |F(w_t) - F_k(w_t)| < eps_t
    all           — FedAvg over everyone (baseline 2)
    priority_only — FedAvg over priority clients (baseline 1)
    topk_align    — budgeted FedALIGN: the k best loss-matched non-priority
                    clients inside the eps band (ties at the k-th rank all
                    enter — deterministic, may exceed k on exact ties)
    grad_sim      — gradient-similarity "friends" selection after Tupitsa
                    et al. (arXiv:2402.05050): include non-priority client k
                    iff cosine(delta_k, delta_P) >= sim_threshold, where
                    delta_P is the priority-weighted mean update
    welfare       — welfare/fairness-aware selection after Travadi et al.
                    (arXiv:2302.08976): gate on the cross-round utility
                    EMAs carried in FederationState (smoothed loss gap
                    within eps_t, or inclusion EMA under the fairness
                    floor)

* **Execution backend** — how the client axis is executed:

    vmap_spatial  — clients in parallel via vmap (clients are mesh shards
                    at pod scale)
    scan_temporal — clients time-multiplexed via lax.scan (models too big
                    to replicate per client)
    scan_async    — overlapped cohorts: spatial (vmap) execution, but the
                    round's aggregated delta is NOT applied at the round
                    barrier. The cohort gathered at round t trains against
                    w_t while later rounds evaluate/gate without waiting
                    for it; its delta lands when the in-flight buffer's pop
                    policy says it is ready (``FedConfig.async_mode``:
                    "fifo" — exactly ``async_depth`` rounds late, the
                    strict pipe; "ready" — FedBuff-style variable lag, any
                    slot aged >= ``min_lag`` pops, oldest first), scaled by
                    its staleness discount (``staleness_decay ** age``,
                    optionally times the measured-drift cosine under
                    ``adaptive_staleness``). The in-flight deltas, their
                    per-slot ages, and the drift-reference sketch are
                    ordinary ``FederationState`` leaves (``state.inflight``
                    / ``state.last_delta``), so the jitted ``lax.scan``
                    driver, checkpoint/resume, and the pjit lowering carry
                    them like any other cross-round state. ``async_depth=0``
                    degenerates to the synchronous round, bit-identical to
                    vmap_spatial; ``async_mode="fifo"`` with
                    ``adaptive_staleness=False`` is bit-identical to the
                    fixed-depth PR 4 pipeline.

  The two synchronous backends produce identical rounds (same PRNG
  fan-out, same gating, same aggregation) — only the schedule over
  hardware differs. ``scan_async`` produces the same *per-round compute*
  but a pipelined *application* schedule.

Rounds thread a persistent **FederationState** — a registered pytree
carrying the global params, the server-optimizer moments, the per-client
overflow backlog, and the per-client utility EMAs. Every round function in
the repo has the signature

    round_fn(state: FederationState, ...) -> (FederationState, stats)

so cross-round behaviour (FedAdam/FedYogi server updates, backlog
fairness, welfare selection, and later staggered/async cohorts) lives in
one seam that survives the jitted ``lax.scan`` driver and checkpoints as
one pytree.

Aggregation routes through `core.aggregation.aggregate_delta`: the whole
client-stacked delta pytree fuses into one [C, M_total] buffer and hits
the `fedagg` kernel once per round (the compiled Pallas kernel on TPU,
its jnp lowering elsewhere; `agg_dtype` casts client deltas on the wire). The
aggregated delta then feeds the decorator-registered ServerOptimizer
(`FedConfig.server_opt`: sgd | momentum | adam | yogi) via
`apply_server_opt` — immediately in the synchronous backends, or
`async_depth` rounds later through the in-flight buffer in `scan_async`.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.aggregation import (aggregate_delta, aggregator_key,
                                    apply_server_opt, check_aggregator_config,
                                    check_codec_config, flatten_stacked,
                                    get_aggregator, inclusion_mass,
                                    resolve_aggregator, resolve_wire_codec,
                                    server_optimizer)
from repro.core.alignment import epsilon_at, global_loss_from_locals
from repro.configs.base import register_validator, validate_config
from repro.optim.schedules import make_schedule
from repro.utils import Registry, fold_in_name, tree_axpy

BACKENDS = ("vmap_spatial", "scan_temporal", "scan_async")


# ============================================================ federation state
@dataclass
class FederationState:
    """Everything FedALIGN carries across the round boundary.

    A registered pytree: jit/scan carries, donation, and
    ``checkpoint/io.py`` all treat it as one tree. Leaf layout is fixed by
    the config (optimizer choice, client count), never by round-time data —
    the pytree-structure stability ``lax.scan`` requires.

    * ``params`` — global model parameters w_t.
    * ``opt_state`` — server-optimizer moments (shape set by
      ``fed.server_opt``: ``()`` for sgd, FedAvgM momentum tree,
      adam/yogi m/v/t).
    * ``backlog`` — [C] int32 rounds each client has been dropped by
      ``max_cohort`` overflow since it last aggregated; wins cohort ties.
    * ``util_ema`` — [C] f32 EMA of the alignment gap |F_k(w_t) - F(w_t)|
      (decay ``fed.utility_ema``), the welfare strategy's utility signal.
    * ``incl_ema`` — [C] f32 EMA of the effective inclusion gates — the
      cross-round participation share welfare fairness reads.
    * ``inflight`` — the ``scan_async`` in-flight cohort buffer, or ``()``
      when ``fed.async_depth == 0``. A dict of three leaves:
      ``inflight["delta"]`` stacks the D = ``fed.async_depth`` aggregated
      cohort deltas awaiting application (params-shaped leaves with a
      leading [D] axis, wire dtype ``fed.agg_dtype``, oldest at index 0),
      ``inflight["valid"]`` is the [D] f32 occupancy mask (valid slots are
      a PREFIX: 0 once the slot has been popped or never filled), and
      ``inflight["age"]`` is the [D] i32 per-slot age — rounds the slot's
      delta has waited since it was pushed. Ages are nonincreasing along
      the ring (slot 0 is oldest), which is what lets the readiness pop
      compact the buffer with one roll.
    * ``last_delta`` — [``fed.sketch_dim``] f32 CountSketch of the most
      recent delta that actually LANDED (nonzero post-clamp scale;
      ``delta_sketch`` under the fixed ``drift_sketch_key`` projection),
      or ``()`` unless ``fed.adaptive_staleness`` asks for drift-measured
      discounts. Kept as a sketch so the extra cross-round state is
      sketch_dim-sized, never params-sized.
    * ``latency`` — the event-driven clock's per-client completion-time
      leaves (``{"compute": [C] f32, "net": [C] f32}``, round units, drawn
      ONCE by ``init_latency``), or ``()`` when ``fed.latency_mode ==
      "none"``. With the clock on, the in-flight dict gains a fourth leaf
      ``inflight["timer"]`` ([D] i32): each slot's countdown, set at push
      time by its slowest surviving member (``slot_timer``) and capped at
      ``ceil(fed.round_deadline)`` — the slot lands when it expires.
    * ``nonfinite_skips`` — scalar i32 count of CONSECUTIVE rounds the
      divergence guard skipped on a non-finite aggregate (reset to 0 by
      any finite round), or ``()`` when ``fed.divergence_guard`` is off.
    * ``ef_accum`` — the per-client error-feedback accumulators of the
      wire codec (``core/aggregation``'s WireCodec registry): params-
      shaped f32 leaves with a leading [C] client axis, each row carrying
      the compression residual x - decode(encode(x)) of that client's
      LAST transmitted delta, re-added to its next delta before encoding.
      ``()`` unless ``fed.wire_codec`` is non-identity AND
      ``fed.error_feedback`` — disabled configs keep the exact legacy
      leaf layout. A row advances when its client's delta is ENCODED
      (push time under ``scan_async``, where aggregation runs at push —
      not when the buffered delta lands), and only with a finite
      residual (a corrupted NaN delta must not poison the accumulator).
    """
    params: Any
    opt_state: Any
    backlog: Any
    util_ema: Any
    incl_ema: Any
    inflight: Any = ()
    last_delta: Any = ()
    latency: Any = ()
    nonfinite_skips: Any = ()
    ef_accum: Any = ()

    def replace(self, **kw) -> "FederationState":
        return dataclasses.replace(self, **kw)


jax.tree_util.register_dataclass(
    FederationState,
    data_fields=["params", "opt_state", "backlog", "util_ema", "incl_ema",
                 "inflight", "last_delta", "latency", "nonfinite_skips",
                 "ef_accum"],
    meta_fields=[])


@register_validator("async")
def check_async_config(fed):
    """Validate the scan_async knobs whose bad values would corrupt the
    in-flight buffer silently (clamped indices) instead of failing.

    Registered as the ``validate_config`` "async" hook; calling it directly
    is deprecated — call ``repro.configs.base.validate_config(fed)``, the
    one entry point that runs every subsystem's checks."""
    if fed.async_depth <= 0:
        return
    if fed.async_mode not in ("fifo", "ready"):
        raise ValueError(f"unknown FedConfig.async_mode {fed.async_mode!r}; "
                         "known: 'fifo' (fixed-lag pipe) | 'ready' "
                         "(variable-lag readiness buffer)")
    if fed.async_mode == "ready" and not 1 <= fed.min_lag <= fed.async_depth:
        raise ValueError(
            f"FedConfig.min_lag={fed.min_lag} outside [1, async_depth="
            f"{fed.async_depth}]: a delta can never age past the buffer "
            "capacity (no slot would ever become ready), and it can never "
            "pop before its first birthday either — the push happens after "
            "the pop phase, so min_lag=0 would silently behave as 1")


@register_validator("clock")
def check_clock_config(fed):
    """Validate the event-clock / deadline / failure-model knobs whose bad
    values would otherwise corrupt rounds silently — a zero or negative
    deadline marks every client late and force-lands every slot with no
    finished members, a rate outside [0, 1] draws garbage Bernoullis.
    Same contract as ``check_async_config``: actionable errors at the
    engine boundary, no-op when everything is disabled. Registered as the
    ``validate_config`` "clock" hook; direct calls are deprecated."""
    lm = fed.latency_mode
    if lm not in ("none", "lognormal"):
        raise ValueError(f"unknown FedConfig.latency_mode {lm!r}; known: "
                         "'none' (no event clock) | 'lognormal' "
                         "(per-client compute + network time draws)")
    if lm != "none":
        if fed.latency_sigma < 0 or fed.latency_net_sigma < 0:
            raise ValueError(
                f"FedConfig.latency_sigma={fed.latency_sigma} / "
                f"latency_net_sigma={fed.latency_net_sigma} must be >= 0 "
                "(they are lognormal log-stds)")
        if fed.async_depth > 0 and fed.async_mode != "ready":
            raise ValueError(
                "the event-driven clock gives every in-flight slot its OWN "
                "countdown (variable lag); async_mode='fifo' constant-folds "
                f"a fixed lag of async_depth={fed.async_depth} rounds and "
                "would ignore the timers — use async_mode='ready'")
    deadline = float(fed.round_deadline)
    if deadline != float("inf"):
        if not deadline > 0:
            raise ValueError(
                f"FedConfig.round_deadline={fed.round_deadline} must be > 0 "
                "(round units): at a zero or negative deadline EVERY client "
                "is late, so every slot would force-land with no finished "
                "members' mass — disable the deadline with float('inf')")
        if lm == "none":
            raise ValueError(
                "FedConfig.round_deadline compares per-client simulated "
                "completion times against the deadline, but "
                "latency_mode='none' draws no completion times — set "
                "latency_mode='lognormal' (or leave round_deadline=inf)")
    name = resolve_failure_model(fed.failure_model)
    if name != "none":
        get_failure_model(name)            # unknown names raise here
        for knob in ("crash_rate", "dropout_rate", "corrupt_rate"):
            v = float(getattr(fed, knob))
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"FedConfig.{knob}={v} outside [0, 1] "
                                 "(a per-client probability)")
        if int(fed.dropout_len) < 1:
            raise ValueError(
                f"FedConfig.dropout_len={fed.dropout_len} must be >= 1 "
                "(rounds per transient drop-out window)")
    if int(fed.max_nonfinite_skips) < 0:
        raise ValueError(
            f"FedConfig.max_nonfinite_skips={fed.max_nonfinite_skips} must "
            "be >= 0 (0 = the divergence guard never halts the run)")


def init_latency(fed, num_clients):
    """Per-client completion-time leaves for the event-driven clock, or
    ``()`` when ``fed.latency_mode == "none"`` (layout fixed by config).

    Drawn ONCE per federation from a named stream off the config seed (the
    main round PRNG chain is untouched): lognormal compute time plus
    lognormal network time, in round units — the systems-heterogeneity
    model of the client-selection survey (arXiv:2211.01549)."""
    if fed.latency_mode == "none":
        return ()
    key = fold_in_name(jax.random.PRNGKey(fed.seed), "latency_model")
    kc, kn = jax.random.split(key)
    C = int(num_clients)
    compute = jnp.exp(fed.latency_mu + fed.latency_sigma
                      * jax.random.normal(kc, (C,), jnp.float32))
    net = jnp.exp(fed.latency_net_mu + fed.latency_net_sigma
                  * jax.random.normal(kn, (C,), jnp.float32))
    return {"compute": compute, "net": net}


def init_inflight(params, fed):
    """Empty in-flight cohort ring buffer for ``fed.async_depth`` (D) slots,
    or ``()`` at depth 0 (synchronous runs carry no extra leaves).

    Leaf layout is fixed by the CONFIG (depth, params shapes, wire dtype,
    and — for the ``timer`` leaf — the latency mode) — the pytree-structure
    stability the scanned driver and checkpoint round-trips require."""
    D = int(fed.async_depth)
    if D <= 0:
        return ()
    ad = jnp.dtype(fed.agg_dtype)
    buf = {
        "delta": jax.tree.map(
            lambda p: jnp.zeros((D,) + tuple(p.shape), ad), params),
        "valid": jnp.zeros((D,), jnp.float32),
        "age": jnp.zeros((D,), jnp.int32),
    }
    if fed.latency_mode != "none":
        # event-driven clock: per-slot countdown (rounds until the slot's
        # slowest surviving member finishes), set at push by slot_timer
        buf["timer"] = jnp.zeros((D,), jnp.int32)
    return buf


def init_last_delta(fed):
    """Zero reference sketch for the drift-adaptive discount, or ``()``
    when ``adaptive_staleness`` is off (layout fixed by the config)."""
    if fed.async_depth > 0 and fed.adaptive_staleness:
        return jnp.zeros((int(fed.sketch_dim),), jnp.float32)
    return ()


def init_ef_accum(params, fed, num_clients):
    """Zero per-client error-feedback accumulators for the wire codec
    (params-shaped f32 leaves with a leading [C] client axis), or ``()``
    when the codec is identity or ``fed.error_feedback`` is off — layout
    fixed by the CONFIG, like every other FederationState leaf."""
    if resolve_wire_codec(getattr(fed, "wire_codec", "identity")) == "identity":
        return ()
    if not fed.error_feedback:
        return ()
    C = int(num_clients)
    return jax.tree.map(
        lambda p: jnp.zeros((C,) + tuple(p.shape), jnp.float32), params)


def init_state(params, fed, num_clients: Optional[int] = None) -> FederationState:
    """Fresh FederationState for a federation of ``num_clients`` (defaults
    to ``fed.num_clients``): zero moments, zero backlog, zero EMAs, and an
    empty in-flight buffer (plus zero drift-reference sketch under
    ``adaptive_staleness``) when ``fed.async_depth > 0``. Latency leaves
    (event clock), the divergence-guard skip counter, and the wire codec's
    error-feedback accumulators exist only when their feature is enabled —
    disabled configs keep the exact legacy leaf layout."""
    validate_config(fed)
    C = int(num_clients if num_clients is not None else fed.num_clients)
    return FederationState(
        params=params,
        opt_state=server_optimizer(fed).init(params),
        backlog=jnp.zeros((C,), jnp.int32),
        util_ema=jnp.zeros((C,), jnp.float32),
        incl_ema=jnp.zeros((C,), jnp.float32),
        inflight=init_inflight(params, fed),
        last_delta=init_last_delta(fed),
        latency=init_latency(fed, C),
        nonfinite_skips=(jnp.zeros((), jnp.int32) if fed.divergence_guard
                         else ()),
        ef_accum=init_ef_accum(params, fed, C))


# ============================================================ selection seam
@dataclass
class SelectionContext:
    """Everything a SelectionStrategy may look at for one round.

    align_vals/global_align are the paper's matching statistic (losses by
    theory, accuracies in the experiments — fed.align_stat). delta_cos is
    only populated when the strategy declares ``needs_deltas`` (it costs a
    [C, M_total] flatten of the client updates, or a CountSketch of them
    under ``fed.grad_sim_sketch``). The cross-round fields
    (backlog/util_ema/incl_ema) come from FederationState: ``util_ema``
    is the BIAS-CORRECTED smoothed gap with THIS round's observation
    already folded in (``utility_estimate``); ``incl_ema`` and
    ``backlog`` describe previous rounds only (gates aren't fixed yet)."""
    align_vals: Any                    # [C] F_k(w_t) (or acc_k(w_t))
    global_align: Any                  # scalar F(w_t)
    eps: Any                           # scalar eps_t
    priority_mask: Any                 # [C] bool
    weights: Any = None                # [C] data fractions p_k
    participation: Any = None          # [C] bool availability, or None
    warmup: Any = False                # scalar bool: inside warm-up rounds
    delta_cos: Any = None              # [C] cosine(delta_k, delta_P)
    topk: int = 4                      # topk_align budget
    sim_threshold: float = 0.0         # grad_sim cosine threshold
    backlog: Any = None                # [C] int32 overflow backlog (state)
    util_ema: Any = None               # [C] bias-corrected loss-gap EMA
                                       # incl. this round's observation
    incl_ema: Any = None               # [C] inclusion EMA (prev. rounds)
    welfare_floor: float = 0.0         # welfare fairness floor on incl_ema


STRATEGIES = Registry("selection strategy")


def register_strategy(name: str, *, needs_deltas: bool = False,
                      warmup_excludes_nonpriority: bool = True):
    """Register ``fn(ctx: SelectionContext) -> [C] float32`` under ``name``.

    The function returns the inclusion vector for NON-priority clients;
    its values at priority positions are ignored. ``needs_deltas`` asks the
    backend to populate ``ctx.delta_cos``. ``warmup_excludes_nonpriority``
    controls whether warm-up rounds force priority-only aggregation (True
    for alignment-style rules; False for the unconditional ``all``)."""
    return STRATEGIES.register(
        name, strategy_name=name, needs_deltas=needs_deltas,
        warmup_excludes_nonpriority=warmup_excludes_nonpriority)


def get_strategy(name: str) -> Callable:
    return STRATEGIES.lookup(name)


@register_strategy("fedalign")
def _fedalign(ctx):
    return (jnp.abs(ctx.align_vals - ctx.global_align) < ctx.eps).astype(jnp.float32)


@register_strategy("all", warmup_excludes_nonpriority=False)
def _all(ctx):
    return jnp.ones(ctx.priority_mask.shape, jnp.float32)


@register_strategy("priority_only")
def _priority_only(ctx):
    return jnp.zeros(ctx.priority_mask.shape, jnp.float32)


@register_strategy("topk_align")
def _topk_align(ctx):
    C = ctx.align_vals.shape[0]
    k = int(ctx.topk)
    if k <= 0:
        return jnp.zeros((C,), jnp.float32)
    diff = jnp.abs(ctx.align_vals - ctx.global_align)
    cand = ~ctx.priority_mask.astype(bool)
    if ctx.participation is not None:
        cand = cand & ctx.participation.astype(bool)
    ranked = jnp.where(cand, diff, jnp.inf)
    kth = jnp.sort(ranked)[min(k, C) - 1]
    return ((ranked <= kth) & (ranked < ctx.eps)).astype(jnp.float32)


@register_strategy("grad_sim", needs_deltas=True)
def _grad_sim(ctx):
    if ctx.delta_cos is None:
        raise ValueError("grad_sim needs ctx.delta_cos (client-update cosine "
                         "similarities); this backend did not provide deltas")
    return (ctx.delta_cos >= ctx.sim_threshold).astype(jnp.float32)


@register_strategy("welfare")
def _welfare(ctx):
    """Welfare/fairness-aware selection (Travadi et al., arXiv:2302.08976):
    include non-priority client k when its SMOOTHED alignment gap (the
    loss-gap EMA, utility of including k for the priority objective) is
    inside the eps band, or when its inclusion EMA has starved below the
    fairness floor. utility_ema=0 degenerates to plain fedalign."""
    if ctx.util_ema is None or ctx.incl_ema is None:
        raise ValueError(
            "welfare needs ctx.util_ema/ctx.incl_ema (cross-round client "
            "utility EMAs from FederationState); this caller is stateless — "
            "thread a FederationState through the round")
    aligned = ctx.util_ema < ctx.eps
    starved = ctx.incl_ema < ctx.welfare_floor
    return (aligned | starved).astype(jnp.float32)


def compute_gates(ctx: SelectionContext, selection: str = "fedalign"):
    """I_{k,t} per client — THE shared gating implementation.

    Priority clients are always included; the strategy decides non-priority
    inclusion; warm-up (strategy-dependent) and participation sampling are
    applied on top."""
    strat = get_strategy(selection)
    pri = ctx.priority_mask.astype(jnp.float32)
    gates = pri + (1.0 - pri) * strat(ctx)
    if strat.warmup_excludes_nonpriority:
        gates = jnp.where(jnp.asarray(ctx.warmup), pri, gates)
    if ctx.participation is not None:
        gates = gates * ctx.participation.astype(jnp.float32)
    return gates


def cosine_to_priority(flat_deltas, weights, priority_mask):
    """[C, M] client deltas -> [C] cosine vs the priority-weighted mean delta
    (the grad_sim statistic; f32 accumulation regardless of input dtype)."""
    f = flat_deltas.astype(jnp.float32)
    wp = weights.astype(jnp.float32) * priority_mask.astype(jnp.float32)
    d_pri = jnp.einsum("c,cm->m", wp, f) / jnp.maximum(jnp.sum(wp), 1e-30)
    dots = f @ d_pri
    norms = jnp.sqrt(jnp.sum(f * f, axis=1)) * jnp.sqrt(jnp.sum(d_pri * d_pri))
    return dots / jnp.maximum(norms, 1e-12)


def cohort_select(gates, align_vals, global_align, priority_mask, k: int,
                  backlog=None, backlog_boost=0.0):
    """Deterministic gather order for the gate-before-train cohort.

    Returns (cohort_idx [K], cohort_gates [K], effective_gates [C]).

    Slots are filled included-first: priority clients, then included
    non-priority clients ranked by alignment match |F_k - F|, then excluded
    clients as zero-gate padding (their slot trains but is dropped by the
    aggregation's gate weighting). Overflow policy — more than K clients
    gate in — drops the WORST-matched non-priority clients this round.
    ``backlog`` ([C] rounds spent dropped by overflow, from
    FederationState) breaks match-quality ties: at equal |F_k - F| the
    longer-starved client wins the slot, so overflow rotates instead of
    permanently starving the same well-aligned clients. At backlog 0 (or
    ``backlog=None``) ties fall back to client index — the original
    drop-worst policy, unchanged. ``backlog_boost`` > 0 promotes backlog
    from tie-breaker to rank term: a non-priority client's rank becomes
    ``|F_k - F| - backlog_boost * backlog``, so a starved client overtakes
    slightly BETTER-matched rivals once its debt grows — float-valued
    match gaps almost never tie exactly, so the pure tie-break cannot
    rotate those cohorts. Priority clients pin to the front regardless of
    any boost; ``backlog_boost=0`` (the default) is bit-identical to the
    tie-break-only policy. ``effective_gates`` is the [C] inclusion
    vector the aggregation actually honours (== ``gates`` when nothing
    overflowed)."""
    pri = priority_mask.astype(bool)
    C = gates.shape[0]
    diff = jnp.abs(align_vals - global_align).astype(jnp.float32)
    bl = (jnp.zeros((C,), jnp.float32) if backlog is None
          else backlog.astype(jnp.float32))
    boost = float(backlog_boost)
    if boost != 0.0:
        # boosted rank: backlog debt buys down the match gap. Priority
        # moves from -1.0 to -inf so no boosted non-priority rank (which
        # can go arbitrarily negative) can ever displace a priority client.
        rank = jnp.where(pri, -jnp.inf,
                         jnp.minimum(diff, 1e30) - jnp.float32(boost) * bl)
    else:
        # python-level branch on the float literal: the boost-off trace is
        # LITERALLY the legacy trace (bit-identity pinned by tests)
        rank = jnp.where(pri, -1.0, jnp.minimum(diff, 1e30))
    key = jnp.where(gates > 0, rank, jnp.inf)
    # lexicographic: (boosted) rank, then backlog (older debts first), then
    # client index — deterministic and identical to the stable argsort of
    # ``key`` whenever every backlog is 0
    order = jnp.lexsort((jnp.arange(C), -bl, key))
    cohort_idx = order[:k]
    cohort_gates = gates[cohort_idx]
    eff_gates = jnp.zeros_like(gates).at[cohort_idx].set(cohort_gates)
    return cohort_idx, cohort_gates, eff_gates


def backlog_update(backlog, gates, eff_gates):
    """Cross-round overflow-fairness ledger: +1 for every client that gated
    in but lost its slot to the cohort budget, reset for clients the
    aggregation honoured, untouched for clients the selection excluded."""
    dropped = (gates > 0) & (eff_gates == 0)
    included = eff_gates > 0
    return jnp.where(dropped, backlog + 1,
                     jnp.where(included, jnp.zeros_like(backlog), backlog))


def utility_update(fed, util_ema, align_vals, global_align):
    """Loss-gap EMA step (decay ``fed.utility_ema``) with this round's
    observation |F_k(w_t) - F(w_t)| folded in. The carried EMA is RAW
    (zero-initialized); consumers debias it with ``utility_estimate``."""
    beta = jnp.float32(fed.utility_ema)
    gap = jnp.abs(align_vals - global_align).astype(jnp.float32)
    return beta * util_ema + (1.0 - beta) * gap


def utility_estimate(fed, util_ema, round_idx):
    """Bias-corrected smoothed gap (adam-style 1 - beta^t divisor).

    The raw zero-initialized EMA UNDERestimates the gap for the first
    ~1/(1-beta) rounds, which would admit badly-misaligned clients into
    the welfare gate early in training; the EMA has been updated
    ``round_idx + 1`` times when the gate reads it (every round updates
    it, warm-up included), so the correction is exact."""
    beta = jnp.float32(fed.utility_ema)
    t = jnp.asarray(round_idx, jnp.float32) + 1.0
    return util_ema / jnp.maximum(1.0 - beta ** t, 1e-12)


def inclusion_update(fed, incl_ema, eff_gates):
    """Inclusion-history EMA step over the EFFECTIVE gates (what the
    aggregation honoured, overflow included)."""
    beta = jnp.float32(fed.utility_ema)
    return beta * incl_ema + (1.0 - beta) * eff_gates.astype(jnp.float32)


def server_delta(fed, global_params, client_params, weights, gates, *,
                 key=None, ef_accum=None):
    """(6a) renormalized gated delta aggregation: one fused fedagg on the
    gated client deltas, honouring ``fed.agg_dtype``'s reduced-precision
    wire format, WITHOUT the ServerOptimizer step. The synchronous round
    applies the result immediately (``apply_server_opt``); the
    ``scan_async`` round pushes it into the in-flight buffer instead
    (``async_apply``) — the reduction runs at PUSH time, so every
    registered ``fed.aggregator`` (robust, dp, cosine-filtered) commutes
    with the buffer for free. ``key`` feeds stochastic aggregators
    (``aggregator_key(fed, round_idx)`` for dp noise).
    ``client_params``/``weights``/``gates`` may live in cohort space
    [K, ...]: zero gates drop padding slots, so the result matches the
    dense [C, ...] aggregation whenever every included client made the
    cohort. With a non-identity ``fed.wire_codec`` and ``ef_accum`` (the
    matching per-client error-feedback rows, cohort-gathered when
    ``client_params`` is) the call returns ``(delta, new_ef_accum)`` —
    because this runs at push time, scan_async's accumulator advances
    when the delta is encoded, not when it lands. THE aggregation-routing
    seam — the sharded pod rounds call it too
    (core/aggregation.aggregate_delta)."""
    return aggregate_delta(global_params, client_params, weights, gates,
                           fed=fed, key=key, ef_accum=ef_accum)


def staleness_discount(fed, age=None):
    """Scale applied to a delta that waited in the in-flight buffer.

    With ``age=None`` (the fifo pipe, where every applied delta aged
    exactly ``fed.async_depth`` rounds) the discount is the compile-time
    python constant ``staleness_decay ** async_depth`` — the PR 4
    semantics, kept constant-folded so the fifo path stays bit-identical.
    With a (traced) ``age`` it is the measured-staleness discount
    ``staleness_decay ** age`` the variable-lag ``ready`` mode uses."""
    if age is None:
        return float(fed.staleness_decay) ** int(fed.async_depth)
    return jnp.float32(fed.staleness_decay) ** age.astype(jnp.float32)


def drift_sketch_key(fed):
    """The ONE projection key for every drift sketch of a run.

    Unlike ``sketch_key`` (grad_sim folds the round index in — each round
    scores clients against each other, never across rounds), drift sketches
    are compared ACROSS rounds (this pop's delta vs the last applied one),
    so every sketch of the run must use the same CountSketch projection or
    their cosine estimates nothing. Derived via ``fold_in_name`` (crc32),
    so the stream is deterministic across processes."""
    from repro.utils import fold_in_name
    return fold_in_name(jax.random.PRNGKey(fed.seed), "async_drift_sketch")


def drift_factor(sketch, last_sketch):
    """max(0, cos(delta, last applied delta)) estimated on CountSketches.

    The clamp at 0 means a stale delta pointing AWAY from where the model
    is currently moving is dropped entirely rather than applied negatively.
    Before any delta has been applied the reference sketch is all-zero —
    no drift evidence — and the factor falls back to 1 (the constant
    schedule alone)."""
    dot = jnp.vdot(sketch.astype(jnp.float32), last_sketch.astype(jnp.float32))
    n_last = jnp.sqrt(jnp.sum(last_sketch.astype(jnp.float32) ** 2))
    n_new = jnp.sqrt(jnp.sum(sketch.astype(jnp.float32) ** 2))
    cos = dot / jnp.maximum(n_new * n_last, 1e-12)
    return jnp.where(n_last > 0, jnp.maximum(cos, 0.0), 1.0)


def _apply_stale(fed, carry, delta, age):
    """Apply ONE popped in-flight delta through the ServerOptimizer with
    its staleness scale. ``carry = (params, opt_state, last_delta)``; runs
    inside ``lax.cond`` on the slot's readiness, so non-popping rounds
    leave params, moments (adam's t included), and the drift reference
    untouched."""
    params, opt_state, last = carry
    # fifo: every pop has aged exactly async_depth rounds -> the python-
    # constant discount (bit-identical to the PR 4 pipeline). ready: the
    # slot's measured age.
    scale = (staleness_discount(fed) if fed.async_mode == "fifo"
             else staleness_discount(fed, age))
    if fed.adaptive_staleness:
        sk = delta_sketch(delta, drift_sketch_key(fed), int(fed.sketch_dim))
        scale = scale * drift_factor(sk, last)
        # the reference advances only when the delta actually moved the
        # model (scale > 0) — raw sketch, direction not scale. A clamped
        # delta must NOT become the reference: with an oscillating stream
        # (+d, -d, +d, ...) it would flip the reference each pop and zero
        # every later update, freezing training while stats still report
        # pops; keeping the last LANDED direction damps the oscillation
        # and lets aligned deltas through.
        last = jnp.where(scale > 0, sk, last)
        # a fully-clamped pop is DROPPED, optimizer included: scale 0
        # through apply_server_opt would still decay momentum (moving
        # params along the stale residual) and tick adam's t — the same
        # moments-untouched invariant warm-up rounds honour applies here
        new_params, new_opt = jax.lax.cond(
            scale > 0,
            lambda s: apply_server_opt(fed, params, opt_state, delta,
                                       scale=s),
            lambda s: (params, opt_state),
            scale)
        return new_params, new_opt, last
    new_params, new_opt = apply_server_opt(fed, params, opt_state, delta,
                                           scale=scale)
    return new_params, new_opt, last


def async_apply(fed, global_params, opt_state, inflight, agg_delta,
                last_delta=(), push_timer=None):
    """One tick of the scan_async application state machine.

    1. Every valid slot ages one round (and, under the event clock, its
       countdown timer ticks down one round).
    2. The READY slots are popped oldest-first and each applied through the
       configured ServerOptimizer with its own staleness scale
       (``_apply_stale``), under ``lax.cond`` per slot — rounds where
       nothing is ready (pipeline warm-up) leave params AND optimizer
       moments untouched. Readiness: ``async_mode="fifo"`` — the slot that
       aged exactly ``async_depth`` rounds (at most one per round, the
       strict PR 4 pipe); ``"ready"`` — every slot whose age reached
       ``min_lag`` (prefix of the ring, possibly several per round); with
       the EVENT CLOCK (``fed.latency_mode != "none"``, the buffer carries
       a ``timer`` leaf) — every slot whose countdown expired, an
       arbitrary subset of the ring since timers are set per slot by the
       cohort's slowest surviving member. A FULL buffer with no ready slot
       force-pops the oldest (the FedBuff overflow rule) so the fresh
       delta always has a slot.
    3. The buffer compacts (one roll for the prefix pops; a stable
       permutation under the clock, where the ready set need not be a
       prefix) and this round's fresh ``agg_delta`` is pushed behind the
       survivors at age 0 — with its countdown set to ``push_timer``
       (``slot_timer``; REQUIRED when the buffer is clocked).

    Returns ``(new_params, new_opt_state, new_inflight, new_last_delta,
    info)`` with ``info = {"applied_valid": popped count (f32),
    "applied_age": oldest applied age (i32, 0 when nothing landed)}``.
    The buffer leaves keep their config-fixed [D, ...] shapes, so the
    whole transition is a legal ``lax.scan`` carry step."""
    valid = inflight["valid"] > 0
    D = int(valid.shape[0])
    age = inflight["age"] + valid.astype(jnp.int32)
    occ = jnp.sum(valid.astype(jnp.int32))
    carry = (global_params, opt_state, last_delta)
    clocked = "timer" in inflight
    if clocked:
        if push_timer is None:
            raise ValueError(
                "this in-flight buffer carries countdown timers "
                "(latency_mode != 'none') but no push_timer was given — "
                "compute one with slot_timer(fed, state.latency, gates)")
        timer = jnp.maximum(inflight["timer"] - valid.astype(jnp.int32), 0)
        # event-driven readiness: a slot lands when its countdown expires,
        # not when it crosses a uniform min_lag — so the ready set is an
        # arbitrary subset of the ring, not a prefix
        ready = valid & (timer <= 0)
        force = (occ >= D) & (jnp.sum(ready.astype(jnp.int32)) == 0)
        ready = ready.at[0].set(ready[0] | force)
        for i in range(D):                 # static unroll: D is small
            delta_i = jax.tree.map(lambda b, i=i: b[i], inflight["delta"])
            carry = jax.lax.cond(
                ready[i],
                lambda c, d=delta_i, i=i: _apply_stale(fed, c, d, age[i]),
                lambda c: c,
                carry)
    elif fed.async_mode == "fifo":
        # single-pop pipe: at most slot 0 can ever be ready (one push per
        # round keeps ages distinct), so the trace holds ONE conditional
        # optimizer apply — not D unrolled copies. The occ >= D term is
        # the same capacity guard the ready branch's force-pop provides.
        ready = jnp.zeros((D,), bool).at[0].set(
            valid[0] & ((age[0] >= int(fed.async_depth)) | (occ >= D)))
        delta0 = jax.tree.map(lambda b: b[0], inflight["delta"])
        carry = jax.lax.cond(
            ready[0],
            lambda c: _apply_stale(fed, c, delta0, age[0]),
            lambda c: c,
            carry)
    else:
        thr = int(fed.min_lag)
        # prefix-closed readiness: ages are nonincreasing along the ring,
        # so "every slot with age >= thr" IS a prefix — the cumprod makes
        # that robust to hand-built states instead of assuming it
        ready = jnp.cumprod((valid & (age >= thr)).astype(jnp.int32)) > 0
        force = (occ >= D) & ~ready[0] & valid[0]
        ready = ready.at[0].set(ready[0] | force)
        for i in range(D):                 # static unroll: D is small
            delta_i = jax.tree.map(lambda b, i=i: b[i], inflight["delta"])
            carry = jax.lax.cond(
                ready[i],
                lambda c, d=delta_i, i=i: _apply_stale(fed, c, d, age[i]),
                lambda c: c,
                carry)
    new_params, new_opt, new_last = carry

    k = jnp.sum(ready.astype(jnp.int32))
    pos = occ - k                          # fresh delta lands behind survivors
    idx = jnp.arange(D)

    if clocked:
        # the ready set need not be a prefix, so compaction is a stable
        # permutation — survivors first in original (push) order — instead
        # of the roll the prefix modes use
        keep = valid & ~ready
        perm = jnp.argsort(jnp.where(keep, idx, idx + D))

        def gather_push(buf, d):
            return jax.lax.dynamic_update_slice_in_dim(
                jnp.take(buf, perm, axis=0), d.astype(buf.dtype)[None], pos,
                axis=0)

        survivor_timer = jnp.where(idx < pos, jnp.take(timer, perm), 0)
        new_inflight = {
            "delta": jax.tree.map(gather_push, inflight["delta"], agg_delta),
            "valid": (idx <= pos).astype(jnp.float32),
            "age": jnp.where(idx < pos, jnp.take(age, perm), 0),
            "timer": jnp.where(idx == pos,
                               jnp.asarray(push_timer, jnp.int32),
                               survivor_timer),
        }
    else:
        def shift_push(buf, d):
            return jax.lax.dynamic_update_slice_in_dim(
                jnp.roll(buf, -k, axis=0), d.astype(buf.dtype)[None], pos,
                axis=0)

        new_inflight = {
            "delta": jax.tree.map(shift_push, inflight["delta"], agg_delta),
            "valid": (idx <= pos).astype(jnp.float32),
            "age": jnp.where(idx < pos, jnp.roll(age, -k), 0),
        }
    info = {"applied_valid": k.astype(jnp.float32),
            "applied_age": jnp.max(jnp.where(ready, age, 0))}
    return new_params, new_opt, new_inflight, new_last, info


def drain_inflight(fed, state: FederationState) -> FederationState:
    """Flush a scan_async pipeline at end of run: apply every still-valid
    in-flight cohort delta oldest-first through the ServerOptimizer — each
    with the discount it would have received in-stream (the constant
    ``staleness_decay ** async_depth`` under fifo, its measured age under
    ``ready``, times the drift factor under ``adaptive_staleness``) — and
    return the state with an emptied buffer. A real async server does
    exactly this at shutdown — straggler cohorts are absorbed, not
    dropped. No-op for synchronous states (``inflight == ()``)."""
    if not isinstance(state.inflight, dict):
        return state
    valid = state.inflight["valid"]
    age = state.inflight["age"]
    carry = (state.params, state.opt_state, state.last_delta)
    D = int(valid.shape[0])
    for i in range(D):                     # static unroll: D is small
        delta_i = jax.tree.map(lambda b, i=i: b[i], state.inflight["delta"])
        carry = jax.lax.cond(
            valid[i] > 0,
            lambda c, d=delta_i, i=i: _apply_stale(fed, c, d, age[i]),
            lambda c: c,
            carry)
    params, opt_state, last = carry
    # zeroing the whole dict keeps whatever leaves the config gave the
    # buffer (the event clock's "timer" leaf included) — layout-stable
    empty = jax.tree.map(jnp.zeros_like, state.inflight)
    return state.replace(params=params, opt_state=opt_state, inflight=empty,
                         last_delta=last)


def delta_sketch(delta, key, dim: int):
    """[dim] CountSketch (sparse Johnson-Lindenstrauss) of a parameter-delta
    pytree: every coordinate lands in one random bucket with a random sign.

    One O(M) pass, no [dim, M] projection matrix is ever materialized — the
    streaming-friendly delta score for grad_sim. The hash/sign streams
    derive from ``key`` and the leaf index only, so every client is
    projected identically and sketched cosines estimate the true delta
    cosines (error ~ 1/sqrt(dim))."""
    out = jnp.zeros((dim,), jnp.float32)
    for i, leaf in enumerate(jax.tree.leaves(delta)):
        x = leaf.reshape(-1).astype(jnp.float32)
        kh, ks = jax.random.split(jax.random.fold_in(key, i))
        h = jax.random.randint(kh, (x.size,), 0, dim)
        s = jax.random.rademacher(ks, (x.size,), dtype=jnp.float32)
        out = out + jax.ops.segment_sum(s * x, h, num_segments=dim)
    return out


def sketch_key(fed, round_idx):
    """Per-round projection key — shared by every client (and by both
    backends, so sketched rounds stay backend-identical)."""
    return jax.random.fold_in(jax.random.PRNGKey(fed.seed ^ 0x5E7C), round_idx)


def participation_mask(fed, key, priority_mask, round_idx, client_ids=None):
    """Paper App. C.3 / A.4: Bernoulli participation sampling (priority set
    never empty) plus straggler cadence (non-priority client k joins every
    2 + k % period rounds).

    ``client_ids`` carries a candidate-pool round's [P] global identities:
    the Bernoulli draw keys on the identity (``fold_in``) and the
    straggler cadence uses the GLOBAL client index, so a client's
    availability schedule is the same whichever pool it got sampled into.
    Dense rounds (``client_ids=None``) keep the legacy shaped draw —
    bit-identical trace."""
    C = priority_mask.shape[0]
    ids = jnp.arange(C) if client_ids is None else client_ids
    if fed.participation < 1.0:
        part = _identity_bernoulli(key, fed.participation, C, client_ids)
        part = part | (jnp.sum(part & priority_mask) == 0) & priority_mask
    else:
        part = jnp.ones((C,), bool)
    if fed.straggler_period > 0:
        cadence = 2 + ids % fed.straggler_period
        available = (round_idx % cadence) == 0
        part = part & (available | priority_mask)
    return part


def pool_select(fed, key, priority_mask, backlog, incl_ema, pool: int):
    """Draw one round's candidate pool: [P] sorted global client indices.

    Priority clients are ALWAYS in-pool (score pinned at +inf); the
    remaining P - num_priority slots go to non-priority clients sampled
    WITHOUT replacement via the Gumbel-top-k trick — score = log(weight) +
    Gumbel noise, take the top P. ``fed.pool_weighting`` sets the weight:

      uniform — every non-priority client equally likely (weight 1)
      backlog — weight 1 + backlog_k: clients starved by cohort overflow
                get sampled back in sooner
      ema     — weight (1 + eps) - incl_ema_k: clients the aggregation has
                rarely honoured get a boost (welfare-style coverage)

    The returned indices are SORTED ascending, so the pool's index space
    is a stable, order-preserving slice of the dense one — the gather /
    scatter contract every pooled round relies on."""
    g = jax.random.gumbel(key, priority_mask.shape, jnp.float32)
    if fed.pool_weighting == "backlog":
        g = g + jnp.log1p(backlog.astype(jnp.float32))
    elif fed.pool_weighting == "ema":
        g = g + jnp.log(jnp.maximum(
            1.0 + 1e-6 - incl_ema.astype(jnp.float32), 1e-6))
    score = jnp.where(priority_mask.astype(bool), jnp.inf, g)
    _, idx = jax.lax.top_k(score, int(pool))
    return jnp.sort(idx)


# ============================================================ failure models
@dataclass
class FailurePlan:
    """One round's fault-injection views, produced by a registered
    FailureModel. A ``None`` field injects nothing — callers branch on
    None at python level, so the fault-free trace stays untouched.

    * ``available`` — [C] bool: clients present this round. Transient
      drop-outs fold into the participation mask, so selection never sees
      an absent client.
    * ``crashed`` — [C] bool: clients that trained but whose delta is LOST
      before aggregation — their slot mass is masked out (partial-cohort
      landing) and the backlog re-enqueues them so they win cohort ties
      when they return.
    * ``corrupt`` — [C] bool: clients whose delta is corrupted in transit
      (NaN'd or scaled rows, injected through the ``delta_transform``
      seam)."""
    available: Any = None
    crashed: Any = None
    corrupt: Any = None


FAILURE_MODELS = Registry("failure model", aliases={None: "none", "": "none"})


def register_failure_model(name: str):
    """Register ``fn(fed, key, round_idx, num_clients, client_ids=None) ->
    FailurePlan`` under ``name`` (decorator, like ``register_strategy`` /
    ``register_aggregator``). ``key`` is the round's failure stream
    (``failure_key``); models must draw ONLY from it (optionally split by
    ``fold_in_name``) so injected faults are bit-reproducible, resume-safe,
    and independent of the main round PRNG chain. ``client_ids`` carries
    the [P] global client identities of a candidate-pool round: with it,
    per-client draws must key on the IDENTITY (``jax.random.fold_in``), so
    a client's fault stream is independent of which pool it landed in."""
    return FAILURE_MODELS.register(name, failure_name=name)


def resolve_failure_model(name) -> str:
    """Canonical failure-model name: None/'' mean 'none' (disabled)."""
    return str(FAILURE_MODELS.resolve(name))


def get_failure_model(name) -> Callable:
    return FAILURE_MODELS.lookup(name)


def failure_key(fed, round_idx):
    """The round's fault-injection PRNG: a named stream off the config seed
    folded with the ABSOLUTE round index. Resuming at round r replays
    exactly the faults the uninterrupted run would have injected, and the
    main round rng chain never advances differently with faults on."""
    base = fold_in_name(jax.random.PRNGKey(fed.seed), "failure_model")
    return jax.random.fold_in(base, round_idx)


def failure_plan(fed, round_idx, num_clients, client_ids=None):
    """Evaluate the configured FailureModel for one round, or None when
    disabled (callers keep the fault-free trace untouched). With
    ``client_ids`` (a candidate-pool round's [P] global identities) the
    plan's masks live in POOL space, drawn per-identity so a client's
    fault stream does not depend on who else got sampled."""
    name = resolve_failure_model(fed.failure_model)
    if name == "none":
        return None
    return FAILURE_MODELS[name](fed, failure_key(fed, round_idx), round_idx,
                                int(num_clients), client_ids=client_ids)


@register_failure_model("none")
def _fm_none(fed, key, round_idx, num_clients, client_ids=None):
    return FailurePlan()


def _identity_bernoulli(key, rate, num_clients, client_ids):
    """[num_clients] Bernoulli draws. Dense rounds (``client_ids=None``)
    keep the legacy one-shot shaped draw (bit-identity); pool rounds key
    each draw on the client IDENTITY via ``fold_in``, so the draw for
    client k is the same whichever pool k landed in — O(P), never O(C)."""
    if client_ids is None:
        return jax.random.bernoulli(key, rate, (num_clients,))
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, client_ids)
    return jax.vmap(lambda k: jax.random.bernoulli(k, rate))(keys)


def _crashed_mask(fed, key, num_clients, client_ids=None):
    return _identity_bernoulli(fold_in_name(key, "crash"),
                               fed.crash_rate, num_clients, client_ids)


def _corrupt_mask(fed, key, num_clients, client_ids=None):
    return _identity_bernoulli(fold_in_name(key, "corrupt"),
                               fed.corrupt_rate, num_clients, client_ids)


def _dropout_available(fed, round_idx, num_clients, client_ids=None):
    # window-stateless draw: one Bernoulli per (window, client), a window
    # spanning dropout_len rounds — the SAME clients sit out every round
    # of the window, reproduced exactly from any resume point (and, under
    # pooling, whichever candidate pools the window's rounds sampled)
    window = round_idx // max(int(fed.dropout_len), 1)
    base = fold_in_name(jax.random.PRNGKey(fed.seed), "failure_dropout")
    k = jax.random.fold_in(base, window)
    return ~_identity_bernoulli(k, fed.dropout_rate, num_clients, client_ids)


@register_failure_model("crash")
def _fm_crash(fed, key, round_idx, num_clients, client_ids=None):
    """Per-round Bernoulli crash: the client trains, then dies before its
    delta reaches the server."""
    return FailurePlan(crashed=_crashed_mask(fed, key, num_clients,
                                             client_ids))


@register_failure_model("dropout")
def _fm_dropout(fed, key, round_idx, num_clients, client_ids=None):
    """Transient drop-out: clients disappear for whole ``dropout_len``-round
    windows (folded into the participation mask)."""
    return FailurePlan(
        available=_dropout_available(fed, round_idx, num_clients, client_ids))


@register_failure_model("corrupt")
def _fm_corrupt(fed, key, round_idx, num_clients, client_ids=None):
    """Delta corruption in transit: NaN'd (``corrupt_scale == 0``) or scaled
    rows, injected through the ``delta_transform`` seam."""
    return FailurePlan(corrupt=_corrupt_mask(fed, key, num_clients,
                                             client_ids))


@register_failure_model("chaos")
def _fm_chaos(fed, key, round_idx, num_clients, client_ids=None):
    """All three fault classes composed. Each draws from its own named
    substream, so chaos with two rates zeroed matches the remaining single
    model bit-for-bit."""
    return FailurePlan(
        available=_dropout_available(fed, round_idx, num_clients, client_ids),
        crashed=_crashed_mask(fed, key, num_clients, client_ids),
        corrupt=_corrupt_mask(fed, key, num_clients, client_ids))


def corruption_transform(fed, corrupt_mask):
    """Build the ``delta_transform`` that poisons the masked clients' trained
    params in transit: ``corrupt_scale == 0`` garbles the payload to NaN
    (what the divergence guard exists to catch); any other value scales the
    delta (a scaled-delta fault the robust aggregators can absorb)."""
    scale = float(fed.corrupt_scale)

    def tf(client_params, global_params, client_idx):
        m = corrupt_mask[client_idx]

        def leaf(cp, gp):
            mm = m.reshape(m.shape + (1,) * (cp.ndim - 1))
            bad = (jnp.full_like(cp, jnp.nan) if scale == 0.0
                   else gp[None] + scale * (cp - gp[None]))
            return jnp.where(mm, bad, cp)

        return jax.tree.map(leaf, client_params, global_params)

    return tf


# ============================================================ event clock
def client_latency(latency):
    """[C] simulated completion time (round units): compute + network."""
    return latency["compute"] + latency["net"]


def lost_mask(fed, state, plan):
    """[C] bool of clients whose trained delta never reaches the server this
    round — crashed, or (under a finite deadline) slower than
    ``fed.round_deadline`` — or None when nothing can be lost (fault-free
    trace untouched). Lost clients keep their SELECTION gates for the
    backlog ledger (+1 this round, so they win cohort ties when they
    return) but contribute zero aggregation mass: the slot lands with only
    its finished members through the zero-mass-safe fedagg path."""
    lost = None
    if plan is not None and plan.crashed is not None:
        lost = plan.crashed
    if (fed.latency_mode != "none"
            and float(fed.round_deadline) != float("inf")):
        late = client_latency(state.latency) > jnp.float32(fed.round_deadline)
        lost = late if lost is None else (lost | late)
    return lost


def aggregate_finite(fed, agg_delta, loss=None):
    """Divergence guard predicate: scalar bool "this round's aggregate may
    touch the model" — every ``agg_delta`` leaf finite AND (when given) the
    eval loss finite — or None when ``fed.divergence_guard`` is off, so
    callers branch at python level and keep the unguarded trace."""
    if not fed.divergence_guard:
        return None
    finite = jnp.asarray(True) if loss is None else jnp.isfinite(loss)
    for leaf in jax.tree.leaves(agg_delta):
        finite = finite & jnp.all(jnp.isfinite(leaf))
    return finite


def skips_update(state, finite):
    """Advance the consecutive non-finite skip counter: +1 on a guarded
    skip, reset on any finite round, pass-through when the guard is off
    (``finite is None``)."""
    if finite is None:
        return state.nonfinite_skips
    return jnp.where(finite, jnp.zeros_like(state.nonfinite_skips),
                     state.nonfinite_skips + 1)


def slot_timer(fed, latency, eff_gates):
    """i32 countdown for the slot pushed this round: the ceiling of its
    slowest SURVIVING included member's completion time, clamped to
    [1, ceil(round_deadline)]. A delta can never land the round it was
    pushed (floor 1); the deadline cap is the force-landing — late members
    were already masked out of ``eff_gates`` by ``lost_mask``, so a capped
    slot carries only its finished members' mass. An all-lost cohort
    pushes an empty (zero-mass) slot with timer 1."""
    t = jnp.max(jnp.where(eff_gates > 0, client_latency(latency), 0.0))
    t = jnp.ceil(t).astype(jnp.int32)
    deadline = float(fed.round_deadline)
    if deadline != float("inf"):
        t = jnp.minimum(t, jnp.int32(math.ceil(deadline)))
    return jnp.maximum(t, 1)


# ============================================================ local training
def local_solver(loss_fn, fed):
    """Returns f(global_params, data, rng, lr) -> local params after E epochs
    of minibatch SGD (or FedProx when fed.algorithm == 'fedprox')."""
    E = fed.local_epochs
    prox_mu = fed.prox_mu if fed.algorithm == "fedprox" else 0.0

    def solve(global_params, data, rng, lr):
        n = data["y"].shape[0]
        bs = min(fed.batch_size, n)
        steps = n // bs

        def epoch(params, ekey):
            perm = jax.random.permutation(ekey, n)[:steps * bs].reshape(steps, bs)

            def step(p, idx):
                batch = jax.tree.map(lambda a: a[idx], data)
                grads = jax.grad(lambda q: loss_fn(q, batch)[0])(p)
                if prox_mu > 0.0:
                    grads = jax.tree.map(lambda g, q, w0: g + prox_mu * (q - w0),
                                         grads, p, global_params)
                return tree_axpy(-lr, grads, p), None

            params, _ = jax.lax.scan(step, params, perm)
            return params, None

        ekeys = jax.random.split(rng, E)
        params, _ = jax.lax.scan(epoch, global_params, ekeys)
        return params

    return solve


# ============================================================ backend seam
def _eval_vmap(loss_fn, params, data):
    return jax.vmap(lambda d: loss_fn(params, d))(data)


def _eval_scan(loss_fn, params, data):
    return jax.lax.map(lambda d: loss_fn(params, d), data)


def _train_vmap(solver, global_params, data, keys, lr, gates=None):
    # vmap lowers lax.cond to a select (both branches execute), so a gate
    # cannot skip work here — the cohort gather is the vmap-side saving.
    return jax.vmap(lambda d, k: solver(global_params, d, k, lr))(data, keys)


def _train_scan(solver, global_params, data, keys, lr, gates=None):
    """Time-multiplexed local training. When ``gates`` is given (known
    before training — gate-before-train strategies), gated-out clients
    skip their E local epochs entirely via lax.cond; their slot returns
    the unmodified global params, which the aggregation drops at gate 0."""
    def body(carry, inp):
        if gates is None:
            d, k = inp
            return carry, solver(global_params, d, k, lr)
        d, k, g = inp
        p = jax.lax.cond(g > 0,
                         lambda: solver(global_params, d, k, lr),
                         lambda: global_params)
        return carry, p

    xs = (data, keys) if gates is None else (data, keys, gates)
    _, stacked = jax.lax.scan(body, 0, xs)
    return stacked


_BACKENDS = {
    "vmap_spatial": (_eval_vmap, _train_vmap),
    "scan_temporal": (_eval_scan, _train_scan),
    # scan_async schedules CLIENTS spatially (vmap) like vmap_spatial — the
    # "scan" in its name is the round axis: cohorts overlap ACROSS rounds
    # of the driver's lax.scan via the in-flight FederationState buffer.
    "scan_async": (_eval_vmap, _train_vmap),
}


# ============================================================ the round
def make_round_fn(loss_fn: Callable, fed, *, backend: Optional[str] = None,
                  delta_transform: Optional[Callable] = None) -> Callable:
    """loss_fn(params, batch) -> (loss, metrics); batch = {'x','y'} (or tokens).

    Returns round_fn(state, data, priority_mask, weights, rng, round_idx)
    -> (new_state, stats), with ``state`` a FederationState (build one with
    ``init_state``). ``data`` leaves have leading client axis [C, n, ...].
    ``backend`` defaults to ``fed.backend``; both backends produce
    identical rounds.

    ``delta_transform(client_params, global_params, client_idx) ->
    client_params`` is an adversarial-injection seam for benchmarks/tests
    ONLY: it rewrites the trained client params right before aggregation
    (``client_idx`` carries client IDENTITIES, so cohort-space rounds can
    target specific clients). The Byzantine attack rows in
    benchmarks/bench_round.py use it to model scaled-delta attackers that
    the loss-gap gate cannot see; production rounds leave it None.

    Round order depends on the strategy. Strategies that gate from the eval
    pre-pass alone (``not needs_deltas``) run **eval -> gates -> train**:
    gates are fixed before any local epoch, so the scan backend cond-skips
    gated-out clients and, when ``fed.max_cohort > 0``, only the K gathered
    included clients train at all (see ``cohort_select`` for the
    backlog-aware overflow policy). Delta-based strategies (grad_sim) keep
    the train-first order — their statistic needs the client updates
    (exact [C, M_total] flatten, or a CountSketch under
    ``fed.grad_sim_sketch``).

    ``backend="scan_async"`` with ``fed.async_depth = D > 0`` defers the
    APPLICATION of the round's aggregated delta through the
    ``FederationState.inflight`` buffer (``async_apply``): round t's
    cohort trains against w_t, later rounds gate without waiting for it,
    and its delta lands once the ``fed.async_mode`` pop policy declares it
    ready — after exactly D rounds ("fifo") or once it aged
    ``fed.min_lag`` rounds ("ready", oldest-first, possibly several per
    round) — scaled by its staleness discount (constant
    ``staleness_decay ** D`` under fifo, measured ``staleness_decay **
    age`` under ready, times the drift cosine when
    ``fed.adaptive_staleness``). At D = 0 the async round degenerates to
    the synchronous one and is bit-identical to ``vmap_spatial``.

    ``fed.candidate_pool = P`` (0 < P < C) decouples population size from
    round cost: the round draws a candidate pool of P clients
    (``pool_select`` — priority always in-pool, non-priority Gumbel-top-k
    sampled from the round PRNG stream), runs eval/gating/cohort/train/
    fedagg on the [P] slice only, and scatter-updates the per-client state
    leaves at the sampled indices — dense [C] leaves are touched by one
    gather and one scatter, so rounds/sec is flat in C. ``candidate_pool
    = 0`` (and P >= C) is the dense round, bit-identical to the legacy
    trace for every strategy x backend."""
    backend = backend or fed.backend
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")
    if fed.async_depth > 0 and backend != "scan_async":
        raise ValueError(
            f"FedConfig.async_depth={fed.async_depth} requires the "
            f"'scan_async' backend; {backend!r} applies every delta at its "
            "own round barrier and would silently ignore the in-flight "
            "buffer (set async_depth=0 or backend='scan_async')")
    validate_config(fed)
    # stochastic aggregators (dp) get a per-round key; deterministic ones
    # keep a key-free trace (python-level branch, not a traced cond)
    agg_needs_key = get_aggregator(fed.aggregator).needs_key
    # fault injection / event clock / divergence guard / wire codec are
    # python-level flags: disabled configs produce literally the
    # fault-free (resp. identity-wire) trace
    failure_on = resolve_failure_model(fed.failure_model) != "none"
    clock_on = fed.latency_mode != "none"
    codec_on = (resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
                != "identity")
    ef_on = codec_on and bool(fed.error_feedback)
    eval_clients, train_clients = _BACKENDS[backend]
    strategy = get_strategy(fed.selection)
    solver = local_solver(loss_fn, fed)
    sched = make_schedule(fed)
    warmup_rounds = int(fed.warmup_frac * fed.rounds)
    gate_before_train = not strategy.needs_deltas
    # static pipeline depth: 0 (and thus the fully synchronous application
    # path, bit-identical to vmap_spatial) unless scan_async asks for more
    async_depth = int(fed.async_depth) if backend == "scan_async" else 0
    # candidate pool size (0 disables); the wrapper below python-branches
    # on it per federation size, so disabled (and P >= C) rounds run the
    # dense body with LITERALLY the legacy trace
    pool = int(getattr(fed, "candidate_pool", 0))

    def _round_body(state: FederationState, data, priority_mask, weights,
                    rng, round_idx, client_ids=None):
        global_params = state.params
        C = priority_mask.shape[0]
        lr = sched(round_idx)
        eps = epsilon_at(fed, round_idx)

        # (2) local loss/accuracy of the *received* model. The paper's
        # experiments (§3.1 "In practice...") match ACCURACIES with eps=0.2;
        # the theory matches losses. Both are supported via fed.align_stat.
        local_losses, local_metrics = eval_clients(loss_fn, global_params, data)
        if fed.align_stat == "accuracy" and "acc" in local_metrics:
            align_vals = local_metrics["acc"]
        else:
            align_vals = local_losses
        # (3) global (priority) statistic F(w_t) resp. acc(w_t)
        g_loss = global_loss_from_locals(local_losses, priority_mask, weights)
        g_align = global_loss_from_locals(align_vals, priority_mask, weights)

        # cross-round utility EMA folds in this round's gap BEFORE gating —
        # the welfare strategy gates on the smoothed signal
        util_ema = utility_update(fed, state.util_ema, align_vals, g_align)

        # participation sampling (paper App. C.3 / A.4)
        rng, pkey = jax.random.split(rng)
        part = participation_mask(fed, pkey, priority_mask, round_idx,
                                  client_ids=client_ids)

        # fault injection: the plan's availability folds into participation
        # (selection never sees a dropped-out client); crashes and
        # deadline-late clients are masked AFTER training (lost_mask);
        # corruption rides the delta_transform seam
        plan = (failure_plan(fed, round_idx, C, client_ids=client_ids)
                if failure_on else None)
        if plan is not None and plan.available is not None:
            part = part & plan.available
        lost = lost_mask(fed, state, plan)
        tf = delta_transform
        if plan is not None and plan.corrupt is not None:
            ctf = corruption_transform(fed, plan.corrupt)
            if delta_transform is None:
                tf = ctf
            else:
                def tf(cp, gp, idx, _user=delta_transform, _ctf=ctf):
                    return _user(_ctf(cp, gp, idx), gp, idx)

        warm = round_idx < warmup_rounds

        # per-client PRNG fan-out is by client IDENTITY (index in [C]), so
        # gathered cohorts train with exactly the keys the dense round uses
        rng, lkey = jax.random.split(rng)
        if client_ids is None:
            lkeys = jax.random.split(lkey, C)
        else:
            # pool rounds fan out by GLOBAL identity in O(P) — splitting C
            # keys would put the population size back on the round's
            # critical path, the exact cost pooling exists to remove
            lkeys = jax.vmap(jax.random.fold_in, (None, 0))(lkey, client_ids)

        akey = aggregator_key(fed, round_idx) if agg_needs_key else None
        # carried error-feedback rows; reassigned by the aggregation site
        # when the codec + EF are on, passed through untouched otherwise
        ef_accum = state.ef_accum

        def make_ctx(delta_cos=None):
            return SelectionContext(
                align_vals=align_vals, global_align=g_align, eps=eps,
                priority_mask=priority_mask, weights=weights,
                participation=part, warmup=warm, delta_cos=delta_cos,
                topk=fed.topk, sim_threshold=fed.sim_threshold,
                backlog=state.backlog,
                util_ema=utility_estimate(fed, util_ema, round_idx),
                incl_ema=state.incl_ema, welfare_floor=fed.welfare_floor)

        if gate_before_train:
            # (4) gates first — they only need the eval pre-pass
            sel_gates = compute_gates(make_ctx(), fed.selection)
            gates = sel_gates
            k = min(int(fed.max_cohort), C) if fed.max_cohort > 0 else 0
            if k > 0:
                # (5) gather-train-scatter: only K cohort slots run E epochs;
                # overflow ties resolve toward the longest-backlogged client
                cohort_idx, cohort_gates, gates = cohort_select(
                    sel_gates, align_vals, g_align, priority_mask, k,
                    backlog=state.backlog,
                    backlog_boost=float(fed.backlog_boost))
                cohort_params = train_clients(
                    solver, global_params,
                    jax.tree.map(lambda a: a[cohort_idx], data),
                    lkeys[cohort_idx], lr, gates=cohort_gates)
                if tf is not None:
                    cohort_params = tf(cohort_params, global_params,
                                       cohort_idx)
                agg_w, agg_g = weights[cohort_idx], cohort_gates
                if lost is not None:
                    # crashed / deadline-late: trained, but the delta never
                    # arrives — mass masked out; sel_gates stay, so the
                    # backlog re-enqueues them (+1, tie-winning on return)
                    keep = 1.0 - lost.astype(jnp.float32)
                    agg_g = agg_g * keep[cohort_idx]
                    gates = gates * keep
                if ef_on:
                    # only the K cohort slots encoded a delta this round:
                    # their EF rows gather with the cohort and scatter back
                    # advanced; everyone else's accumulator is untouched
                    cohort_ef = jax.tree.map(lambda a: a[cohort_idx],
                                             state.ef_accum)
                    agg_delta, cohort_ef = server_delta(
                        fed, global_params, cohort_params, agg_w, agg_g,
                        key=akey, ef_accum=cohort_ef)
                    ef_accum = jax.tree.map(
                        lambda full, sub: full.at[cohort_idx].set(sub),
                        state.ef_accum, cohort_ef)
                else:
                    agg_delta = server_delta(fed, global_params,
                                             cohort_params, agg_w, agg_g,
                                             key=akey)
            else:
                # (5) dense: everyone trains, but the scan backend still
                # cond-skips gated-out clients (no epochs for gate 0)
                client_params = train_clients(solver, global_params, data,
                                              lkeys, lr, gates=gates)
                if tf is not None:
                    client_params = tf(client_params, global_params,
                                       jnp.arange(C))
                if lost is not None:
                    gates = gates * (1.0 - lost.astype(jnp.float32))
                agg_w, agg_g = weights, gates
                if ef_on:
                    agg_delta, ef_accum = server_delta(
                        fed, global_params, client_params, agg_w, agg_g,
                        key=akey, ef_accum=state.ef_accum)
                else:
                    agg_delta = server_delta(fed, global_params,
                                             client_params, agg_w, agg_g,
                                             key=akey)
        else:
            # (5) train-first: the statistic needs the client updates
            sel_gates = None
            client_params = train_clients(solver, global_params, data, lkeys, lr)
            if tf is not None:
                # before the delta statistic on purpose: a realistic attacker
                # influences grad_sim scores with the very delta it submits
                client_params = tf(client_params, global_params,
                                   jnp.arange(C))
            deltas = jax.tree.map(lambda ck, g: ck - g[None],
                                  client_params, global_params)
            if fed.grad_sim_sketch:
                # streamed-friendly score: CountSketch each delta instead of
                # the exact [C, M_total] flatten (same projection per client)
                skey = sketch_key(fed, round_idx)
                sketches = jax.vmap(
                    lambda d: delta_sketch(d, skey, int(fed.sketch_dim)))(deltas)
                delta_cos = cosine_to_priority(sketches, weights, priority_mask)
            else:
                delta_cos = cosine_to_priority(flatten_stacked(deltas),
                                               weights, priority_mask)
            # (4) gates from the selection strategy (core/alignment rule et al.)
            gates = compute_gates(make_ctx(delta_cos), fed.selection)
            sel_gates = gates
            if lost is not None:
                gates = gates * (1.0 - lost.astype(jnp.float32))
            agg_w, agg_g = weights, gates
            if ef_on:
                agg_delta, ef_accum = server_delta(
                    fed, global_params, client_params, agg_w, agg_g,
                    key=akey, ef_accum=state.ef_accum)
            else:
                agg_delta = server_delta(fed, global_params, client_params,
                                         agg_w, agg_g, key=akey)

        # divergence guard: a non-finite aggregate (poisoned delta, loss
        # overflow) must never touch params or optimizer moments — and a
        # non-finite EVAL loss means the model already diverged, so its
        # delta is not trusted either
        finite = aggregate_finite(fed, agg_delta, g_loss)

        # (6) apply — at the round barrier (sync, and scan_async at depth
        # 0), or through the in-flight buffer's readiness policy
        # (scan_async: fixed fifo lag, variable-lag "ready" pops, or the
        # event clock's per-slot countdown timers)
        if async_depth > 0:
            if finite is not None:
                # a non-finite aggregate must not enter the buffer: zero it
                # so the slot lands as a bit-exact no-op contribution
                agg_delta = jax.tree.map(
                    lambda d: jnp.where(finite, d, jnp.zeros_like(d)),
                    agg_delta)
            push_timer = (slot_timer(fed, state.latency, gates)
                          if clock_on else None)
            new_global, opt_state, inflight, last_delta, ainfo = async_apply(
                fed, global_params, state.opt_state, state.inflight,
                agg_delta, last_delta=state.last_delta,
                push_timer=push_timer)
        else:
            # zero-inclusion rounds (every gate 0 — e.g. participation
            # sampling missed everyone outside warm-up) must be true no-ops:
            # running the optimizer on the all-zero delta would still decay
            # momentum and tick adam/yogi's step count. Skip the whole
            # ServerOptimizer apply when the aggregator's inclusion mass is
            # zero — or, under the divergence guard, when the aggregate is
            # non-finite — leaving params AND moments bit-identical.
            mass = inclusion_mass(fed, agg_w, agg_g)
            pred = mass > 0
            if finite is not None:
                pred = pred & finite
            new_global, opt_state = jax.lax.cond(
                pred,
                lambda: apply_server_opt(fed, global_params, state.opt_state,
                                         agg_delta),
                lambda: (global_params, state.opt_state))
            inflight = state.inflight
            last_delta = state.last_delta

        nonfinite_skips = skips_update(state, finite)

        # cross-round state: backlog ledger + inclusion EMA follow the
        # EFFECTIVE gates the aggregation honoured
        backlog = backlog_update(state.backlog,
                                 gates if sel_gates is None else sel_gates,
                                 gates)
        incl_ema = inclusion_update(fed, state.incl_ema, gates)
        new_state = FederationState(params=new_global, opt_state=opt_state,
                                    backlog=backlog, util_ema=util_ema,
                                    incl_ema=incl_ema, inflight=inflight,
                                    last_delta=last_delta,
                                    latency=state.latency,
                                    nonfinite_skips=nonfinite_skips,
                                    ef_accum=ef_accum)

        npri = (1.0 - priority_mask.astype(jnp.float32))
        included_mass = jnp.sum(npri * weights * gates)
        stats = {
            "round": round_idx,
            "lr": lr,
            "eps": eps,
            "global_loss": g_loss,
            "local_losses": local_losses,
            "gates": gates,
            "backlog": backlog,
            "theta_round": 1.0 / (1.0 + included_mass),   # paper eq. (7) term
            "included_nonpriority": jnp.sum(npri * gates),
            "warmup": warm.astype(jnp.int32) if hasattr(warm, "astype") else jnp.int32(warm),
        }
        if async_depth > 0:
            # async-only keys (python-level branch: the depth-0 trace stays
            # literally the vmap_spatial trace). "staleness" is the MEASURED
            # age of the oldest delta applied this round — 0 on rounds where
            # nothing landed (pipeline warm-up included), so loss-curve
            # tooling never attributes warm-up rounds to stale updates.
            stats["staleness"] = ainfo["applied_age"]
            stats["applied_valid"] = ainfo["applied_valid"]
            stats["inflight_occupancy"] = jnp.sum(inflight["valid"])
        if lost is not None:
            # survivor accounting: how many clients this round trained but
            # never delivered (crash + deadline-late)
            stats["lost_clients"] = jnp.sum(lost.astype(jnp.float32))
        if fed.divergence_guard:
            # consecutive non-finite skips — run_federation halts-and-
            # reports once this crosses fed.max_nonfinite_skips
            stats["skipped_nonfinite"] = nonfinite_skips
        return new_state, stats

    def round_fn(state: FederationState, data, priority_mask, weights, rng,
                 round_idx):
        C = priority_mask.shape[0]
        # python branch on static shapes: candidate_pool = 0 (disabled) and
        # candidate_pool >= C both fall through to the dense body — the
        # parity guarantee is trivially the identity of traces
        if not 0 < pool < C:
            return _round_body(state, data, priority_mask, weights, rng,
                               round_idx)
        # the pool key is split FIRST (only on this branch), so the rest of
        # the round consumes the same per-purpose chain order as dense
        # rounds: participation, then local keys
        rng, pool_key = jax.random.split(rng)
        pool_idx = pool_select(fed, pool_key, priority_mask, state.backlog,
                               state.incl_ema, pool)

        def take(a):
            return a[pool_idx]

        # [P] view of the federation: per-client leaves gather at the
        # sampled indices, global leaves (params, moments, in-flight
        # buffer, drift sketch, skip counter) pass through untouched
        view = state.replace(
            backlog=take(state.backlog),
            util_ema=take(state.util_ema),
            incl_ema=take(state.incl_ema),
            latency=(jax.tree.map(take, state.latency) if clock_on
                     else state.latency),
            ef_accum=(jax.tree.map(take, state.ef_accum) if ef_on
                      else state.ef_accum))
        sub, stats = _round_body(
            view, jax.tree.map(take, data), take(priority_mask),
            take(weights), rng, round_idx, client_ids=pool_idx)

        # scatter the pool's per-client leaves back at the sampled
        # indices; every out-of-pool row is bit-identical to before the
        # round (pinned by tests/test_pool.py)
        new_state = sub.replace(
            backlog=state.backlog.at[pool_idx].set(sub.backlog),
            util_ema=state.util_ema.at[pool_idx].set(sub.util_ema),
            incl_ema=state.incl_ema.at[pool_idx].set(sub.incl_ema),
            latency=state.latency,      # read-only: drawn once at init
            ef_accum=(jax.tree.map(
                lambda full, s: full.at[pool_idx].set(s),
                state.ef_accum, sub.ef_accum) if ef_on else state.ef_accum))
        # per-client stats scatter to the dense [C] layout (out-of-pool
        # rows report 0) so loss-curve tooling keeps one index space
        for name in ("local_losses", "gates"):
            stats[name] = (jnp.zeros((C,), stats[name].dtype)
                           .at[pool_idx].set(stats[name]))
        stats["backlog"] = new_state.backlog
        stats["pool_idx"] = pool_idx
        return new_state, stats

    return round_fn
