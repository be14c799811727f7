"""Partition-spec assignment for parameters, batches and caches.

A name-rule + divisibility-fallback engine: leaf names carry layout intent
(column-parallel for input projections, row-parallel for output
projections, expert/tensor parallel for MoE); whenever the preferred dim is
not divisible by the mesh axis, the engine falls back to the largest
divisible dim, then to replication. This keeps every one of the 10
architectures lowering on the same (data, model) / (pod, data, model)
meshes without per-arch hand specs — per-arch overrides then become pure
performance knobs (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name -> preferred dim (negative = from the end) for the MODEL axis
_MODEL_DIM_RULES: list[tuple[str, int]] = [
    (r"^(wq|wk|wv|bq|bk|bv|wq_b|wkv_b|w_gate|w_up|b_up|w_in|w_gates|b_gates|"
     r"w_dtproj|lm_head|conv_w|conv_b)$", -1),
    (r"^(wo|w_out|w_xproj|w_if)$", 0),
    (r"^(w_down|b_down)$", 0),          # 2D [dff, d]; 3D handled below
    (r"^(embed|pos_dec|pos_enc)$", 0),  # vocab/position dim; fallback -> d
    (r"^(dt_bias|D|gn_scale)$", 0),
]

_REPLICATE = re.compile(r"^(scale|bias|w_router|A_log|r_gates|b_if|wq_a|wkv_a)$")

COLLECTIVE_AXES_DOC = """model axis: tensor parallel; data axis: client/DP
(+ FSDP for flagged archs); pod axis: extra client parallelism (params are
replicated across pods, gradients/updates cross pods only in the FedALIGN
aggregation all-reduce)."""


def dp_axes(mesh: Mesh) -> tuple:
    """Mesh axes carrying clients / data parallelism."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def tp_axes(mesh: Mesh) -> tuple:
    """Mesh axes carrying tensor (model) parallelism."""
    return tuple(a for a in ("model",) if a in mesh.axis_names)


def _stack_offset(path) -> int:
    """Leaves under 'periods' / stacked inits carry a leading stack axis."""
    for k in path:
        if getattr(k, "key", None) in ("periods", "enc_blocks", "dec_blocks"):
            return 1
    return 0


def _leaf_name(path) -> str:
    for k in reversed(path):
        key = getattr(k, "key", None)
        if isinstance(key, str):
            return key
    return ""


def _try_assign(spec: list, shape, dim: int, axis: str, size: int) -> bool:
    if dim < 0:
        dim += len(shape)
    if 0 <= dim < len(shape) and spec[dim] is None \
            and shape[dim] % size == 0 and shape[dim] >= size:
        spec[dim] = axis
        return True
    return False


def _fallback_assign(spec: list, shape, axis: str, size: int,
                     skip: tuple = ()) -> bool:
    cands = [i for i in range(len(shape))
             if spec[i] is None and i not in skip
             and shape[i] % size == 0 and shape[i] >= size]
    if not cands:
        return False
    i = max(cands, key=lambda j: shape[j])
    spec[i] = axis
    return True


def _param_spec(path, leaf, mesh: Mesh, *, fsdp: bool,
                expert_parallel: bool) -> P:
    name = _leaf_name(path)
    off = _stack_offset(path)
    shape = leaf.shape[off:]
    spec: list = [None] * len(shape)
    msize = mesh.shape["model"]

    if not _REPLICATE.match(name) and len(shape) > 0:
        placed = False
        # MoE expert tensors [E, d, f] / [E, f, d]
        if len(shape) == 3 and name in ("w_gate", "w_up", "w_down"):
            if expert_parallel and shape[0] % msize == 0:
                placed = _try_assign(spec, shape, 0, "model", msize)
            if not placed:
                dim = 1 if name == "w_down" else 2     # the dff dim
                placed = _try_assign(spec, shape, dim, "model", msize)
        if not placed:
            for pat, dim in _MODEL_DIM_RULES:
                if re.match(pat, name):
                    placed = _try_assign(spec, shape, dim, "model", msize)
                    break
        if not placed:
            placed = _fallback_assign(spec, shape, "model", msize)
        if fsdp and len(shape) >= 2 and "data" in mesh.axis_names:
            _fallback_assign(spec, shape, "data", mesh.shape["data"])

    return P(*([None] * off + spec))


def auto_param_specs(param_shapes, mesh: Mesh, *, fsdp: bool = False,
                     expert_parallel: bool = False):
    """param_shapes: pytree of ShapeDtypeStruct/arrays -> pytree of P."""
    paths_leaves = jax.tree_util.tree_flatten_with_path(param_shapes)[0]
    treedef = jax.tree_util.tree_structure(param_shapes)
    specs = [_param_spec(p, l, mesh, fsdp=fsdp, expert_parallel=expert_parallel)
             for p, l in paths_leaves]
    return jax.tree_util.tree_unflatten(treedef, specs)


def auto_batch_specs(batch_shapes, mesh: Mesh, *, batch_dim: int = 0):
    """Shard the batch dim over (pod, data) when divisible, else replicate."""
    dp = dp_axes(mesh)
    dpsize = 1
    for a in dp:
        dpsize *= mesh.shape[a]

    def one(leaf):
        shape = leaf.shape
        spec = [None] * len(shape)
        if len(shape) > batch_dim and shape[batch_dim] % dpsize == 0 \
                and shape[batch_dim] >= dpsize:
            spec[batch_dim] = dp
        return P(*spec)
    return jax.tree.map(one, batch_shapes)


def auto_tree_specs(shapes, mesh: Mesh, *, prefer_batch_dim: int = 0,
                    model_dim_order: str = "largest"):
    """Generic (e.g. KV caches): batch dim over dp when divisible, model on
    a remaining divisible dim, else dp on largest (long caches).

    model_dim_order:
      'largest' — largest divisible dim (decode caches: shards the long cache axis)
      'last'    — innermost dims first (prefill cache OUTPUTS: k/v leave the
                  projections sharded on KV*hd, so S-sharding the stored
                  cache would force an in-loop reshard — granite: 2.6x
                  collective regression, see EXPERIMENTS.md SSPerf)
    """
    dp = dp_axes(mesh)
    dpsize = 1
    for a in dp:
        dpsize *= mesh.shape[a]
    msize = mesh.shape["model"]

    def one(path, leaf):
        shape = leaf.shape
        off = _stack_offset(path)
        body = shape[off:]
        spec: list = [None] * len(body)
        used_dp = False
        if len(body) > prefer_batch_dim and body[prefer_batch_dim] % dpsize == 0 \
                and body[prefer_batch_dim] >= dpsize:
            spec[prefer_batch_dim] = dp
            used_dp = True
        if len(body) > 1:
            if model_dim_order == "last":
                placed = False
                for dim in range(len(body) - 1, prefer_batch_dim, -1):
                    if _try_assign(spec, body, dim, "model", msize):
                        placed = True
                        break
                if not placed:
                    _fallback_assign(spec, body, "model", msize,
                                     skip=(prefer_batch_dim,))
            else:
                _fallback_assign(spec, body, "model", msize,
                                 skip=(prefer_batch_dim,))
        if not used_dp and len(body) > 1:
            _fallback_assign(spec, body, dp, dpsize, skip=(prefer_batch_dim,))
        return P(*([None] * off + spec))

    paths_leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in paths_leaves])


def round_batch_specs(batch_shapes, mesh: Mesh, *, fsdp: bool):
    """PartitionSpecs for a pod round's batch (``launch.train.build_batches``
    layout). Client leaves are [C, b, ...]: the spatial round shards the
    client axis C over (pod, data) — each shard trains its own clients —
    and the temporal round scans C and shards the inner batch b instead.
    Server leaves shard their batch dim; the [C] priority mask and weights
    replicate. Any dim not divisible by the data-parallel size replicates."""
    client_dim = 1 if fsdp else 0
    return {
        "clients": auto_batch_specs(batch_shapes["clients"], mesh,
                                    batch_dim=client_dim),
        "server": auto_batch_specs(batch_shapes["server"], mesh),
        "priority_mask": P(),
        "weights": P(),
    }


def shaped_with(shapes, specs, mesh: Mesh):
    """Attach NamedShardings to a ShapeDtypeStruct pytree (for .lower)."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        shapes, specs)


def federation_state_specs(fed, param_specs):
    """PartitionSpec pytree for a ``fl.engine.FederationState``.

    Server-optimizer moments are params-shaped and inherit the matching
    param's spec (FSDP'd moments for FSDP'd params); the [C] client-state
    vectors (backlog, utility EMAs) and scalar step counters replicate —
    they are a few bytes and every pod reads them in the gate. The
    ``scan_async`` in-flight buffer (``fed.async_depth`` stacked aggregated
    deltas) is params-shaped behind its leading ring-buffer axis, so every
    delta slot shards exactly like the param it will eventually update —
    the buffer adds D x params of sharded bytes, never a replicated copy.

    ``fed.candidate_pool`` changes NOTHING here on purpose: pooling adds
    no FederationState leaves — the dense [C] client vectors keep their
    replicated specs and are touched only by the pool wrapper's gather /
    scatter, so the same spec tree covers pooled and dense runs (the
    resume-safety of the pool knobs rides the checkpoint fingerprint
    instead, see ``fl.simulator._state_fingerprint``)."""
    from repro.core.aggregation import resolve_server_opt
    from repro.fl.engine import FederationState

    name = resolve_server_opt(fed.server_opt)
    rep = P()
    if name == "sgd" or (name == "momentum" and fed.server_momentum == 0.0):
        # optim.sgd collapses momentum=0 to the stateless update -> ()
        opt_specs = ()
    elif name == "momentum":
        opt_specs = {"m": param_specs}
    else:                                   # adam / yogi: m, v, step counter
        opt_specs = {"m": param_specs, "v": param_specs, "t": rep}
    if fed.async_depth > 0:
        # per-slot ages ([D] i32) replicate like the validity mask: every
        # pod reads them in the readiness pop
        inflight_specs = {
            "delta": jax.tree.map(
                lambda sp: P(*([None] + list(sp))), param_specs,
                is_leaf=lambda x: isinstance(x, P)),
            "valid": rep,
            "age": rep,
        }
        if fed.latency_mode != "none":
            # event-clock countdowns ([D] i32) replicate like the ages
            inflight_specs["timer"] = rep
    else:
        inflight_specs = ()
    # the drift-reference sketch is [sketch_dim] — a few KB — so it
    # replicates; only the delta slots are params-sized and sharded
    last_delta_specs = (rep if fed.async_depth > 0 and fed.adaptive_staleness
                        else ())
    # event-clock latency leaves are [C] f32 client vectors — replicated
    # like the backlog/EMAs; the divergence-guard skip counter is a scalar
    latency_specs = ({"compute": rep, "net": rep}
                     if fed.latency_mode != "none" else ())
    skips_specs = rep if fed.divergence_guard else ()
    # wire-codec error-feedback accumulators are params-shaped behind a
    # leading [C] client axis — exactly the in-flight delta layout, and
    # for the same reason: C x params of residual rows must shard like
    # the params they re-enter, never hold a replicated copy per pod
    from repro.core.aggregation import resolve_wire_codec
    if (resolve_wire_codec(getattr(fed, "wire_codec", "identity"))
            != "identity" and fed.error_feedback):
        ef_specs = jax.tree.map(
            lambda sp: P(*([None] + list(sp))), param_specs,
            is_leaf=lambda x: isinstance(x, P))
    else:
        ef_specs = ()
    return FederationState(params=param_specs, opt_state=opt_specs,
                           backlog=rep, util_ema=rep, incl_ema=rep,
                           inflight=inflight_specs,
                           last_delta=last_delta_specs,
                           latency=latency_specs,
                           nonfinite_skips=skips_specs,
                           ef_accum=ef_specs)
