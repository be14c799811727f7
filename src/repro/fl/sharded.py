"""Pod-scale FedALIGN: the communication round as a single pjit program.

Two execution modes, chosen by device memory (``choose_round``):

* **spatial** — clients ARE the (pod, data) mesh shards. Client-stacked
  params [C, ...] are vmapped through E local SGD steps in parallel; the
  gated aggregation contracts the client axis, lowering to ONE all-reduce
  over (pod, data) — FedALIGN's entire server communication.

* **temporal** — for models too large to replicate per client (jamba-398b,
  llava-34b): params stay (data, model)-sharded (FSDP+TP); the client
  cohort is traversed with lax.scan, each client running its local steps
  on the full mesh; gated updates accumulate in the scan carry. The
  federation semantics are identical — clients are time-multiplexed
  instead of space-multiplexed.

Both rounds have the engine's persistent-state signature

    round_step(state: engine.FederationState, batch, round_idx=0)
        -> (new_state, stats)

so server-optimizer moments (``fed.server_opt``), the ``max_cohort``
overflow backlog, the welfare utility EMAs, and the ``scan_async``
in-flight cohort buffer thread through pod rounds exactly as through the
in-silico simulator. ``fed.async_depth = D > 0`` runs BOTH pod modes with
overlapped cohorts: the round aggregates as usual but its delta enters the
``FederationState.inflight`` buffer and whichever buffered deltas the
``fed.async_mode`` pop policy declares ready (the slot that aged exactly D
rounds under "fifo"; every slot aged >= ``min_lag``, oldest first, under
the FedBuff-style "ready") are applied instead, each staleness-discounted
by its own age — and by its measured drift under
``fed.adaptive_staleness`` (``engine.async_apply`` — the same state
machine as the engine's ``scan_async`` backend, so pod rounds and the
simulator stay drift-free).

The server statistic F(w_t) is computed on a server-held global batch
(paper §3.1: "the server transmits ... also its associated loss"), so the
gate needs no second pass over clients. Gating itself comes from the
SelectionStrategy registry in fl/engine.py — the SAME implementation the
in-silico simulator uses, as is the cohort gather order
(``engine.cohort_select``: one overflow/backlog policy, no pod/simulator
drift). Both modes gate BEFORE training wherever the strategy allows it
(``not needs_deltas``): the temporal scan fixes gates from a cheap eval
pre-pass (one forward per client, negligible next to E local steps) and
wraps each streamed client's training in ``lax.cond(gate > 0, ...)`` so
gated-out FSDP clients skip their E local steps entirely; the spatial
round, when ``fed.max_cohort > 0``, gathers the included clients into a
dense [K, ...] cohort and trains only those. Delta-based strategies
(grad_sim) keep the train-first order; the temporal round requires
``fed.grad_sim_sketch=True`` and scores streamed clients on a CountSketch
random projection of their delta (``engine.delta_sketch``, width
``fed.sketch_dim``) — the [C, sketch_dim] sketch buffer replaces the
impossible [C, M_total] flatten — then re-runs the (deterministic) local
steps of included clients in a second cond-skipped scan once the gates
are known. The opt-in is explicit because the sketch is JL-approximate:
with it set, the spatial round scores on the same sketches, so the two
modes stay gate-identical; without it, exact cosines exist only
spatially and the temporal round refuses rather than silently diverge.

Each phase of a round runs under a named scope (``jax.named_scope``),
so a profile names the phase that spent each second:
``fedalign.server_loss`` (the server statistic F(w_t)),
``fedalign.eval`` (the clients' loss of the received model),
``fedalign.gate`` (utility, gates, cohort and pool selection, failure
masks, delta sketches), ``fedalign.train`` (E local steps),
``fedalign.aggregate`` (the streamed carry or ``engine.server_delta``)
and ``fedalign.server_step`` (the divergence guard, the server optimizer
or in-flight buffer, the next state and stats). Phases never nest: a
scan whose body holds several phases is called outside any of them, so
its loop bookkeeping carries none. The scopes only name operations; the
compiled program is the same with or without them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import validate_config
from repro.core.aggregation import (aggregator_key, apply_server_opt,
                                    flatten_stacked, get_aggregator,
                                    inclusion_mass, resolve_aggregator,
                                    resolve_wire_codec)
from repro.core.alignment import epsilon_at
from repro.fl import engine
from repro.kernels import ops as kops
from repro.models import moe
from repro.sharding.specs import dp_axes, tp_axes
from repro.utils import fold_in_name, tree_axpy, tree_sub

FSDP_ARCHS = {"jamba-1.5-large-398b", "llava-next-34b"}
PHASES = ("server_loss", "eval", "gate", "train", "aggregate", "server_step")


def needs_fsdp(cfg) -> bool:
    return cfg.name in FSDP_ARCHS


def _fits(compiled, bytes_limit) -> bool:
    """Whether a compiled program's bytes (arguments, temporaries, and
    outputs not aliased to a donated argument) fit ``bytes_limit``."""
    mem = compiled.memory_analysis()
    if bytes_limit is None or mem is None:
        return True
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return need <= bytes_limit


def choose_round(cfg, compile_round, bytes_limit):
    """Pick the round from memory by compiling it: the spatial round where
    its program fits the device's ``bytes_limit``, else the temporal
    (client-streaming) round. A spatial compile that runs out of device
    memory counts as not fitting. The archs ``needs_fsdp`` names go
    straight to the temporal round; no reported limit (CPU) keeps the
    spatial one. ``compile_round(fsdp)`` returns the compiled round;
    returns ``(fsdp, compiled)``."""
    if not needs_fsdp(cfg):
        try:
            compiled = compile_round(False)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
        else:
            if _fits(compiled, bytes_limit):
                return False, compiled
    return True, compile_round(True)


def _context_mesh():
    """The multi-device mesh the round is traced under (``jax.set_mesh``),
    or None on one device."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _kernels_per_shard(round_step):
    """Trace ``round_step`` with its Pallas kernels placed per shard of the
    context mesh (XLA cannot partition a Mosaic kernel): batch dims over
    the data-parallel axes, heads over the model axes. Client-vmapped
    bodies narrow this (``_client_vmap``)."""
    @functools.wraps(round_step)
    def step(*args, **kwargs):
        mesh = _context_mesh()
        axes = ((), ()) if mesh is None else (dp_axes(mesh), tp_axes(mesh))
        with kops.kernels_per_shard(mesh, *axes):
            return round_step(*args, **kwargs)
    return step


def _client_vmap(fn):
    """``jax.vmap(fn)`` over the leading client axis. On a multi-device
    mesh whose data-parallel axes divide the client count, the client
    axis maps over them (``spmd_axis_name``): each shard trains its own
    clients, and the Pallas kernels inside stay client-local (their own
    batch dims whole on the shard), so no activation crosses chips."""
    def mapped(*args):
        mesh = _context_mesh()
        dp = () if mesh is None else dp_axes(mesh)
        n = jax.tree.leaves(args)[0].shape[0]
        if not dp or n % math.prod(mesh.shape[a] for a in dp):
            return jax.vmap(fn)(*args)

        def local(*a):
            with kops.kernels_per_shard(mesh, (), tp_axes(mesh)):
                return fn(*a)
        return jax.vmap(local, spmd_axis_name=dp)(*args)
    return mapped


def _phase(name):
    """The named scope of one round phase, ``fedalign.<name>``. Each call
    site scopes its own phase; a scoped region holds no other phase."""
    assert name in PHASES, name
    return jax.named_scope(f"fedalign.{name}")


def _loss(model, params, batch):
    """(loss, the expert layer's counters) of one forward; the counters are
    {} for a model without expert layers, so that its round carries
    nothing more."""
    loss, metrics = model.loss_fn(params, batch)
    return loss, {k: metrics[k] for k in moe.COUNTERS if k in metrics}


def _train_steps(model, params, batch, lr, n_steps):
    """E local SGD steps on one client's batch (deterministic: full-batch
    gradients, no PRNG — re-running them reproduces the update exactly).
    Returns (params', the counters of the E forwards)."""
    def step(p, _):
        (_, counts), grads = jax.value_and_grad(
            lambda q: _loss(model, q, batch), has_aux=True)(p)
        return tree_axpy(-lr, grads, p), counts

    params, counts = jax.lax.scan(step, params, None, length=n_steps)
    return params, moe.fold_counts(counts)


def _local_steps(model, params, batch, lr, n_steps):
    """Local training plus F_k(w_t) of the *received* model (the paper's
    matching statistic). Returns (params', loss0, counters)."""
    with _phase("eval"):
        loss0, counts = _loss(model, params, batch)
    with _phase("train"):
        params, train_counts = _train_steps(model, params, batch, lr, n_steps)
        return params, loss0, moe.add_counts(counts, train_counts)


def _gate_ctx(fed, state, util_ema, local_losses, server_loss, pm, w,
              delta_cos=None, round_idx=0, participation=None):
    """SelectionContext for one pod-scale round. ``round_idx`` threads the
    driver's round counter into the eps schedule (eps_t via ``epsilon_at``);
    drivers that never pass it keep the t=0 value (== fed.epsilon).
    ``util_ema`` is the updated RAW loss-gap EMA (this round's observation
    folded in) — the strategy sees its bias-corrected estimate;
    backlog/incl_ema come straight from the FederationState.
    ``participation`` carries the failure model's availability mask
    (transient drop-outs) — None keeps the everyone-present gate."""
    return engine.SelectionContext(
        align_vals=local_losses, global_align=server_loss,
        eps=epsilon_at(fed, round_idx), priority_mask=pm, weights=w,
        participation=participation,
        delta_cos=delta_cos, topk=fed.topk, sim_threshold=fed.sim_threshold,
        backlog=state.backlog,
        util_ema=engine.utility_estimate(fed, util_ema, round_idx),
        incl_ema=state.incl_ema, welfare_floor=fed.welfare_floor)


def _next_state(fed, state, new_params, opt_state, sel_gates, eff_gates,
                util_ema, inflight=None, last_delta=None,
                nonfinite_skips=None, ef_accum=None):
    """Advance the cross-round carry with THE engine update rules."""
    return engine.FederationState(
        params=new_params, opt_state=opt_state,
        backlog=engine.backlog_update(state.backlog, sel_gates, eff_gates),
        util_ema=util_ema,
        incl_ema=engine.inclusion_update(fed, state.incl_ema, eff_gates),
        inflight=state.inflight if inflight is None else inflight,
        last_delta=state.last_delta if last_delta is None else last_delta,
        latency=state.latency,
        nonfinite_skips=(state.nonfinite_skips if nonfinite_skips is None
                         else nonfinite_skips),
        ef_accum=state.ef_accum if ef_accum is None else ef_accum)


def _apply_delta(fed, state, params, agg_delta, mass=None, push_timer=None,
                 finite=None):
    """Apply an aggregated global delta the way the engine would: at the
    round barrier when ``fed.async_depth == 0``, or through the
    FederationState in-flight buffer's pop policy (``engine.async_apply``,
    THE staleness state machine — fifo pipe, variable-lag readiness pops,
    or the event clock's per-slot countdowns via ``push_timer``) when the
    pod round runs overlapped cohorts. ``mass`` is the aggregator's
    inclusion mass for the round (``aggregation.inclusion_mass`` / the
    temporal round's streamed denominator): when given, a zero-mass round
    skips the ServerOptimizer entirely — params AND moments stay
    bit-identical instead of momentum decaying on an all-zero delta.
    ``finite`` is the divergence-guard predicate (``engine
    .aggregate_finite``): a non-finite aggregate is skipped the same
    bit-exact way (sync) or zeroed before it enters the buffer (async).
    Returns (new_params, opt_state, inflight, last_delta, info | None)."""
    if fed.async_depth > 0:
        if finite is not None:
            agg_delta = jax.tree.map(
                lambda d: jnp.where(finite, d, jnp.zeros_like(d)), agg_delta)
        return engine.async_apply(fed, params, state.opt_state,
                                  state.inflight, agg_delta,
                                  last_delta=state.last_delta,
                                  push_timer=push_timer)
    pred = None if mass is None else mass > 0
    if finite is not None:
        pred = finite if pred is None else pred & finite
    if pred is None:
        new_params, opt_state = apply_server_opt(fed, params, state.opt_state,
                                                 agg_delta)
    else:
        new_params, opt_state = jax.lax.cond(
            pred,
            lambda: apply_server_opt(fed, params, state.opt_state, agg_delta),
            lambda: (params, state.opt_state))
    return new_params, opt_state, state.inflight, state.last_delta, None


def _async_stats(fed, stats, info, inflight):
    """Async-only stat keys (python-level branch: synchronous pod rounds
    keep their exact stats structure). "staleness" reports the MEASURED
    age of the oldest delta applied this round — 0 when nothing landed
    (warm-up rounds), never the constant pipeline depth."""
    if fed.async_depth > 0:
        stats["staleness"] = info["applied_age"]
        stats["applied_valid"] = info["applied_valid"]
        stats["inflight_occupancy"] = jnp.sum(inflight["valid"])
    return stats


def _failure_stats(fed, stats, lost, nonfinite_skips):
    """Failure-model / divergence-guard stat keys (python-level branches,
    like ``_async_stats``): survivor accounting + consecutive skips."""
    if lost is not None:
        stats["lost_clients"] = jnp.sum(lost.astype(jnp.float32))
    if fed.divergence_guard:
        stats["skipped_nonfinite"] = nonfinite_skips
    return stats


def _server_step(fed, state, agg_delta, mass, server_loss, local_losses,
                 sel_gates, gates, util_ema, ef_accum, lost, pm, w, counts):
    """The round's last phase, shared by both pod rounds: the divergence
    guard, the server optimizer (or the in-flight buffer), the next
    FederationState and the round's stats, with the expert layer's
    ``counts`` over the round's forwards (the server loss, the eval
    pre-pass and the local steps whose update is aggregated) where the
    model has one. Returns (new_state, stats)."""
    clock_on = fed.latency_mode != "none"
    finite = engine.aggregate_finite(fed, agg_delta, server_loss)
    push_timer = (engine.slot_timer(fed, state.latency, gates)
                  if clock_on and fed.async_depth > 0 else None)
    new_params, opt_state, inflight, last_delta, applied = _apply_delta(
        fed, state, state.params, agg_delta, mass=mass,
        push_timer=push_timer, finite=finite)
    new_state = _next_state(fed, state, new_params, opt_state,
                            sel_gates, gates, util_ema, inflight=inflight,
                            last_delta=last_delta,
                            nonfinite_skips=engine.skips_update(state, finite),
                            ef_accum=ef_accum)
    stats = _async_stats(fed, {
        "server_loss": server_loss,
        "local_losses": local_losses,
        "gates": gates,
        "backlog": new_state.backlog,
        "theta_round": 1.0 / (1.0 + jnp.sum((1 - pm.astype(jnp.float32)) * w * gates)),
    }, applied, inflight)
    stats = _failure_stats(fed, stats, lost, new_state.nonfinite_skips)
    stats.update(counts)
    return new_state, stats


def pool_round_key(fed, round_idx):
    """The pod rounds take no rng argument, so the candidate-pool draw is a
    NAMED stream off the config seed folded with the ABSOLUTE round index —
    deterministic across processes (crc32 ``fold_in_name``), resume-safe
    (round r redraws r's exact pool), and independent of the failure /
    aggregator / latency streams."""
    base = fold_in_name(jax.random.PRNGKey(fed.seed), "candidate_pool")
    return jax.random.fold_in(base, round_idx)


def _pool_wrap(fed, round_step):
    """Candidate-pool wrapper shared by both pod rounds: sample P of the C
    clients (``engine.pool_select`` — priority always in-pool), run the
    wrapped round on the [P] gather of the batch and the per-client state
    leaves, and scatter the updated leaves back at the sampled indices.
    The pool slice keeps the existing mesh layout: client-sharded leaves
    gather into [P] shards, shard-local aggregation runs unchanged, and
    the cross-pod reduce stays the one [M_total] all-reduce.

    ``candidate_pool = 0`` (and P >= C) returns the wrapped round itself —
    the dense trace, bit-identical to the legacy pod round."""
    pool = int(getattr(fed, "candidate_pool", 0))
    if pool <= 0:
        return round_step
    clock_on = fed.latency_mode != "none"
    ef_on = (resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
             != "identity") and bool(fed.error_feedback)

    def pooled_step(state, batch, round_idx=0):
        pm = batch["priority_mask"]
        C = pm.shape[0]
        if pool >= C:
            return round_step(state, batch, round_idx)
        with _phase("gate"):
            pool_idx = engine.pool_select(fed, pool_round_key(fed, round_idx),
                                          pm, state.backlog, state.incl_ema,
                                          pool)

            def take(a):
                return a[pool_idx]

            view = state.replace(
                backlog=take(state.backlog),
                util_ema=take(state.util_ema),
                incl_ema=take(state.incl_ema),
                latency=(jax.tree.map(take, state.latency) if clock_on
                         else state.latency),
                ef_accum=(jax.tree.map(take, state.ef_accum) if ef_on
                          else state.ef_accum))
            sub_batch = dict(batch)
            sub_batch["clients"] = jax.tree.map(take, batch["clients"])
            sub_batch["priority_mask"] = take(pm)
            sub_batch["weights"] = take(batch["weights"])
        sub, stats = round_step(view, sub_batch, round_idx,
                                client_ids=pool_idx)
        with _phase("gate"):
            new_state = sub.replace(
                backlog=state.backlog.at[pool_idx].set(sub.backlog),
                util_ema=state.util_ema.at[pool_idx].set(sub.util_ema),
                incl_ema=state.incl_ema.at[pool_idx].set(sub.incl_ema),
                latency=state.latency,      # read-only: drawn once at init
                ef_accum=(jax.tree.map(
                    lambda full, s: full.at[pool_idx].set(s),
                    state.ef_accum, sub.ef_accum) if ef_on
                    else state.ef_accum))
            # per-client stats keep the dense [C] index space downstream
            # tooling expects; out-of-pool rows report 0
            for name in ("local_losses", "gates"):
                stats[name] = (jnp.zeros((C,), stats[name].dtype)
                               .at[pool_idx].set(stats[name]))
        stats["backlog"] = new_state.backlog
        stats["pool_idx"] = pool_idx
        return new_state, stats

    return pooled_step


def make_spatial_round(model, fed, num_clients: int):
    """Returns round_step(state, batch, round_idx=0) -> (new_state, stats).

    batch: client-stacked arrays [C, b, ...] + server_* arrays (global data).
    priority_mask/weights [C] ride inside batch so everything is one pytree.

    Gate-before-train: for strategies that gate from losses of the received
    model alone (``not needs_deltas``) and ``fed.max_cohort > 0``, an eval
    pre-pass fixes the gates, the K included clients are gathered into a
    dense [K, ...] cohort (``engine.cohort_select`` — backlog-aware
    overflow), and only they run their E local steps — round cost O(K*E)
    instead of O(C*E). grad_sim keeps the train-first order.
    """
    E = fed.local_epochs
    lr = fed.lr
    validate_config(fed)
    agg_needs_key = get_aggregator(fed.aggregator).needs_key
    strategy = engine.get_strategy(fed.selection)
    use_cohort = fed.max_cohort > 0 and not strategy.needs_deltas
    failure_on = engine.resolve_failure_model(fed.failure_model) != "none"
    # the wire codec is shard-local: each pod shard encodes its own client
    # rows and the fused kernel decodes-and-reduces per shard — the single
    # cross-shard all-reduce stays on the [M_total] aggregate, unchanged
    codec_on = (resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
                != "identity")
    ef_on = codec_on and bool(fed.error_feedback)

    def round_step(state, batch, round_idx=0, client_ids=None):
        params = state.params
        client_batch = batch["clients"]
        pm = batch["priority_mask"]
        w = batch["weights"]
        C = pm.shape[0]

        with _phase("server_loss"):
            server_loss, counts = _loss(model, params, batch["server"])
        ef_accum = state.ef_accum

        # fault injection mirrors the engine round: availability folds into
        # the selection context, crashes/deadline-late clients are masked
        # AFTER training (lost_mask), corruption rides the same transform.
        # client_ids (a pooled round's [P] global identities) keys the
        # fault draws on the IDENTITY, pool-independent
        with _phase("gate"):
            plan = (engine.failure_plan(fed, round_idx, C,
                                        client_ids=client_ids)
                    if failure_on else None)
            part = (plan.available if plan is not None
                    and plan.available is not None else None)
            lost = engine.lost_mask(fed, state, plan)
            ctf = (engine.corruption_transform(fed, plan.corrupt)
                   if plan is not None and plan.corrupt is not None else None)

        if use_cohort:
            # eval -> gates -> gather-train: only K cohort slots pay E steps
            with _phase("eval"):
                local_losses, eval_counts = _client_vmap(
                    lambda cb: _loss(model, params, cb))(client_batch)
            with _phase("gate"):
                util_ema = engine.utility_update(fed, state.util_ema,
                                                 local_losses, server_loss)
                sel_gates = engine.compute_gates(
                    _gate_ctx(fed, state, util_ema, local_losses, server_loss,
                              pm, w, round_idx=round_idx, participation=part),
                    fed.selection)
                idx, cg, gates = engine.cohort_select(
                    sel_gates, local_losses, server_loss, pm,
                    min(fed.max_cohort, C), backlog=state.backlog,
                    backlog_boost=float(fed.backlog_boost))
                cohort_batch = jax.tree.map(lambda a: a[idx], client_batch)
            with _phase("train"):
                cohort_params, train_counts = _client_vmap(
                    lambda cb: _train_steps(model, params, cb, lr, E))(
                    cohort_batch)
                counts = moe.add_counts(counts, moe.add_counts(
                    moe.fold_counts(eval_counts),
                    moe.fold_counts(train_counts)))
            with _phase("gate"):
                if ctf is not None:
                    cohort_params = ctf(cohort_params, params, idx)
                agg_w, agg_g = w[idx], cg
                if lost is not None:
                    # crashed / deadline-late: trained, but the delta never
                    # arrives — mass masked out; sel_gates stay, so the
                    # backlog re-enqueues them (+1, tie-winning on return)
                    keep = 1.0 - lost.astype(jnp.float32)
                    agg_g = agg_g * keep[idx]
                    gates = gates * keep
            with _phase("aggregate"):
                akey = aggregator_key(fed, round_idx) if agg_needs_key else None
                if ef_on:
                    # only the K gathered slots encoded a delta: their EF
                    # rows gather with the cohort, scatter back advanced
                    cohort_ef = jax.tree.map(lambda a: a[idx], state.ef_accum)
                    agg_delta, cohort_ef = engine.server_delta(
                        fed, params, cohort_params, agg_w, agg_g, key=akey,
                        ef_accum=cohort_ef)
                    ef_accum = jax.tree.map(
                        lambda full, sub: full.at[idx].set(sub),
                        state.ef_accum, cohort_ef)
                else:
                    agg_delta = engine.server_delta(fed, params, cohort_params,
                                                    agg_w, agg_g, key=akey)
        else:
            client_params, local_losses, client_counts = _client_vmap(
                lambda cb: _local_steps(model, params, cb, lr, E))(client_batch)
            with _phase("train"):
                counts = moe.add_counts(counts, moe.fold_counts(client_counts))
            with _phase("gate"):
                util_ema = engine.utility_update(fed, state.util_ema,
                                                 local_losses, server_loss)
                if ctf is not None:
                    # before the delta statistic, matching the engine: a
                    # realistic attacker influences grad_sim scores with the
                    # very delta it submits
                    client_params = ctf(client_params, params, jnp.arange(C))

                delta_cos = None
                if strategy.needs_deltas:
                    deltas = jax.tree.map(lambda ck, g: ck - g[None],
                                          client_params, params)
                    if fed.grad_sim_sketch:
                        skey = engine.sketch_key(fed, round_idx)
                        sketches = jax.vmap(lambda d: engine.delta_sketch(
                            d, skey, int(fed.sketch_dim)))(deltas)
                        delta_cos = engine.cosine_to_priority(sketches, w, pm)
                    else:
                        delta_cos = engine.cosine_to_priority(
                            flatten_stacked(deltas), w, pm)

                sel_gates = gates = engine.compute_gates(
                    _gate_ctx(fed, state, util_ema, local_losses, server_loss,
                              pm, w, delta_cos, round_idx=round_idx,
                              participation=part),
                    fed.selection)
                if lost is not None:
                    gates = gates * (1.0 - lost.astype(jnp.float32))
            agg_w, agg_g = w, gates
            with _phase("aggregate"):
                akey = aggregator_key(fed, round_idx) if agg_needs_key else None
                if ef_on:
                    agg_delta, ef_accum = engine.server_delta(
                        fed, params, client_params, agg_w, agg_g, key=akey,
                        ef_accum=state.ef_accum)
                else:
                    agg_delta = engine.server_delta(fed, params, client_params,
                                                    agg_w, agg_g, key=akey)
        with _phase("server_step"):
            return _server_step(fed, state, agg_delta,
                                inclusion_mass(fed, agg_w, agg_g),
                                server_loss, local_losses, sel_gates, gates,
                                util_ema, ef_accum, lost, pm, w, counts)

    return _kernels_per_shard(_pool_wrap(fed, round_step))


def make_temporal_round(model, fed, cohort: int):
    """FSDP variant: scan over a client cohort; accumulate gated updates.

    batch['clients'] leaves are [C, b, ...] with C the SCAN axis (unsharded);
    the inner batch dim b is sharded over (pod, data).

    Delta-based strategies (grad_sim) stream too: a first scan trains each
    client and keeps only a [sketch_dim] CountSketch of its delta
    (``engine.delta_sketch`` — the projection, never the [C, M_total]
    deltas, crosses the scan), cosines against the priority-weighted mean
    sketch fix the gates, and a second cond-skipped scan re-runs the
    deterministic local steps of the included clients to accumulate their
    gated updates. Cost: one extra pass of E local steps for included
    clients — the price of scoring without materializing per-client deltas.

    **Robust/private aggregators gather the client axis.** The streaming
    weighted-sum carry above only exists for the (linear) gated mean;
    coordinate-wise trimmed_mean/median are order statistics ACROSS
    clients, dp clips on whole-delta norms, and cosine_filter compares
    client directions — none decompose into a running sum. With
    ``fed.aggregator != "mean"`` the scan therefore stacks every client's
    trained params into a [C, ...] carry — a deliberate resharding, the
    one place the temporal round pays spatial-round memory — and routes
    them through
    ``engine.server_delta`` (the same fused fedagg call as the spatial
    round, so the two pod modes stay bit-comparable per aggregator).
    """
    E = fed.local_epochs
    lr = fed.lr
    validate_config(fed)
    codec_on = (resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
                != "identity")
    ef_on = codec_on and bool(fed.error_feedback)
    # a non-identity wire codec also forces the gather: it encodes per-
    # client ROWS of the fused [C, M_total] buffer (row max-abs scales,
    # row top-k, row sketches), which the streamed (num, den) mean carry
    # never materializes — the codec path IS the fused fedagg seam
    robust_gather = resolve_aggregator(fed.aggregator) != "mean" or codec_on
    agg_needs_key = get_aggregator(fed.aggregator).needs_key
    strategy = engine.get_strategy(fed.selection)
    failure_on = engine.resolve_failure_model(fed.failure_model) != "none"
    if (engine.resolve_failure_model(fed.failure_model) in ("corrupt", "chaos")
            and fed.corrupt_rate > 0):
        raise ValueError(
            f"failure model {fed.failure_model!r} with corrupt_rate="
            f"{fed.corrupt_rate} poisons trained params in transit, but the "
            "temporal (FSDP) round streams clients through a scan carry and "
            "has no per-client materialization to corrupt on the linear "
            "path — use the spatial round for corruption faults, or set "
            "corrupt_rate=0 (crash/drop-out faults stream fine)")
    if strategy.needs_deltas and not fed.grad_sim_sketch:
        raise ValueError(
            f"selection {fed.selection!r} needs client deltas; the temporal "
            "(FSDP) round streams clients and can only score them on a "
            "CountSketch of their delta — set FedConfig.grad_sim_sketch=True "
            "(and size sketch_dim) to opt in to the JL-approximate statistic "
            "(the spatial round then sketches too, keeping the modes "
            "identical), or use the spatial round for exact cosines")

    def round_step(state, batch, round_idx=0, client_ids=None):
        params = state.params
        pm = batch["priority_mask"]
        w = batch["weights"]
        C = pm.shape[0]
        with _phase("server_loss"):
            server_loss, counts = _loss(model, params, batch["server"])
        ef_accum = state.ef_accum
        no_counts = jax.tree.map(jnp.zeros_like, counts)

        # fault injection (corruption excluded above): availability masks
        # selection, crashes/deadline-late clients lose their mass
        # post-train; client_ids keys pooled draws on the global identity
        with _phase("gate"):
            plan = (engine.failure_plan(fed, round_idx, C,
                                        client_ids=client_ids)
                    if failure_on else None)
            part = (plan.available if plan is not None
                    and plan.available is not None else None)
            lost = engine.lost_mask(fed, state, plan)

        # eval pre-pass: F_k(w_t) for the whole cohort before any gate is
        # fixed (rank-based strategies need the full loss vector)
        with _phase("eval"):
            local_losses, eval_counts = jax.lax.map(
                lambda cb: _loss(model, params, cb), batch["clients"])
            counts = moe.add_counts(counts, moe.fold_counts(eval_counts))
        with _phase("gate"):
            util_ema = engine.utility_update(fed, state.util_ema,
                                             local_losses, server_loss)

        # a scan whose body holds several phases is called outside any
        # phase: its body scopes each of them
        delta_cos = None
        if strategy.needs_deltas:
            # pass 1: train each streamed client, keep only its delta sketch
            skey = engine.sketch_key(fed, round_idx)
            dim = int(fed.sketch_dim)

            def sketch_client(carry, cbatch):
                with _phase("train"):
                    p_k, _ = _train_steps(model, params, cbatch, lr, E)
                with _phase("gate"):
                    return carry, engine.delta_sketch(tree_sub(p_k, params),
                                                      skey, dim)

            _, sketches = jax.lax.scan(sketch_client, 0, batch["clients"])
            with _phase("gate"):
                delta_cos = engine.cosine_to_priority(sketches, w, pm)

        with _phase("gate"):
            sel_gates = gates = engine.compute_gates(
                _gate_ctx(fed, state, util_ema, local_losses, server_loss,
                          pm, w, delta_cos, round_idx=round_idx,
                          participation=part),
                fed.selection)
            if lost is not None:
                # a lost streamed client's delta never reaches the carry, so
                # it may as well skip its E local steps (gate 0 cond-skips);
                # its SELECTION gate stays for the backlog re-enqueue
                gates = gates * (1.0 - lost.astype(jnp.float32))

        def train_if_gated(cbatch, gate):
            # gates are fixed before the scan, so gated-out streamed clients
            # skip their E local steps entirely (cond, not select: scan
            # bodies are traced once and branch at run time)
            with _phase("train"):
                return jax.lax.cond(
                    gate > 0,
                    lambda b: _train_steps(model, params, b, lr, E),
                    lambda b: (params, no_counts), cbatch)

        if robust_gather:
            # robust/private aggregators need every client's delta at once
            # (order statistics / whole-delta norms / direction cosines):
            # stack the trained params into a [C, ...] carry — the
            # documented resharding — and reduce through THE fused fedagg
            # seam.
            def per_client_stack(stacked, inp):
                k, cbatch, gate = inp
                p_k, c_k = train_if_gated(cbatch, gate)
                with _phase("aggregate"):
                    return jax.tree.map(
                        lambda s, p: jax.lax.dynamic_update_index_in_dim(
                            s, p, k, 0), stacked, p_k), c_k

            with _phase("aggregate"):
                empty = jax.tree.map(
                    lambda p: jnp.zeros((C,) + p.shape, p.dtype), params)
            stacked, train_counts = jax.lax.scan(
                per_client_stack, empty,
                (jnp.arange(C), batch["clients"], gates))
            with _phase("aggregate"):
                akey = aggregator_key(fed, round_idx) if agg_needs_key else None
                if ef_on:
                    agg_delta, ef_accum = engine.server_delta(
                        fed, params, stacked, w, gates, key=akey,
                        ef_accum=state.ef_accum)
                else:
                    agg_delta = engine.server_delta(fed, params, stacked, w,
                                                    gates, key=akey)
                mass = inclusion_mass(fed, w, gates)
        else:
            def per_client(carry, inp):
                acc_num, acc_den = carry
                cbatch, w_k, gate = inp
                p_k, c_k = train_if_gated(cbatch, gate)
                with _phase("aggregate"):
                    wg = w_k * gate
                    acc_num = jax.tree.map(
                        lambda a, pk: a + wg * pk.astype(jnp.float32),
                        acc_num, p_k)
                    return (acc_num, acc_den + wg), c_k

            with _phase("aggregate"):
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (num, den), train_counts = jax.lax.scan(
                per_client, (zeros, jnp.float32(0)),
                (batch["clients"], w, gates))
            # streamed aggregation accumulates f32 in the carry; the
            # aggregated DELTA then feeds the same ServerOptimizer step as
            # the fused path (or the in-flight buffer, when the round runs
            # overlapped cohorts). A zero-mass round yields an EXACT zero
            # delta (num/1e-30 - params would be -params, wiping the model).
            mass = den
            with _phase("aggregate"):
                agg_delta = jax.tree.map(
                    lambda n, p: jnp.where(
                        den > 0,
                        n / jnp.maximum(den, 1e-30) - p.astype(jnp.float32),
                        0.0),
                    num, params)
        with _phase("server_step"):
            counts = moe.add_counts(counts, moe.fold_counts(train_counts))
            return _server_step(fed, state, agg_delta, mass, server_loss,
                                local_losses, sel_gates, gates, util_ema,
                                ef_accum, lost, pm, w, counts)

    return _kernels_per_shard(_pool_wrap(fed, round_step))


def make_round_step(model, fed, num_clients: int, *, fsdp: bool):
    return (make_temporal_round(model, fed, num_clients) if fsdp
            else make_spatial_round(model, fed, num_clients))


def capture_round_program(model, fed, num_clients: int, batch, *,
                          fsdp: bool = False, round_idx: int = 0):
    """Package the pod round-step for static analysis without executing
    (or even materializing) anything:

        step, args, meta = sharded.capture_round_program(model, fed, C, batch)
        report = repro.analysis.lint_program(step, args, fed, meta=meta)

    ``batch`` may be real arrays or ShapeDtypeStructs (dryrun-style); the
    FederationState is built abstractly via ``jax.eval_shape``. ``meta``
    carries the wire width and ``pod=True`` so the collective-budget rule
    holds the round to its single-all-reduce promise (mean path) or the
    documented client-axis-gather allowance (order statistics / coded
    wires)."""
    from repro.utils import param_count
    step = make_round_step(model, fed, num_clients, fsdp=fsdp)
    state = jax.eval_shape(lambda: engine.init_state(
        model.init(jax.random.PRNGKey(0)), fed, num_clients))
    meta = {"m_total": param_count(state.params),
            "num_clients": num_clients, "rounds": 1, "pod": True}

    def fn(state, batch):
        return step(state, batch, round_idx=round_idx)

    return fn, (state, batch), meta


# ----------------------------------------------------------------- serving
def make_prefill_step(model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def make_serve_step(model):
    def serve_step(params, caches, tokens, pos):
        return model.decode_step(params, caches, tokens, pos)
    return serve_step
