"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, with memory/cost analysis and collective-bytes
extraction for the roofline (EXPERIMENTS.md SS Dry-run / SS Roofline).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
        --shape train_4k [--multi-pod] [--out results/dryrun]

No real arrays are ever allocated: params/batches/caches enter as
jax.ShapeDtypeStruct with NamedShardings attached. ``main`` gives the CPU
backend 512 host devices; importing this module changes nothing.
"""
import argparse
import gzip
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, ALIASES, INPUT_SHAPES, get_config
from repro.configs.base import FedConfig
from repro.configs.cli import add_fed_args, fed_from_args
from repro.fl import sharded
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.sharding.specs import (auto_batch_specs, auto_param_specs,
                                  auto_tree_specs, dp_axes,
                                  federation_state_specs, round_batch_specs,
                                  shaped_with)
from repro.utils import param_count

# shape-point skips with reasons (DESIGN.md SS4)
SKIPS = {
    ("whisper-medium", "long_500k"):
        "enc-dec audio: bounded decoder context; 524k-token transcript has no analogue",
}

# archs needing a sliding-window variant to run long_500k sub-quadratically
WINDOW_FOR_LONG = 8192

DRYRUN_FED = FedConfig(local_epochs=5, epsilon=0.2, lr=0.01)
TEMPORAL_COHORT = 4


def adapt_config(cfg, shape_name: str):
    """Per-shape config adjustments (documented in DESIGN.md)."""
    if shape_name == "long_500k" and cfg.pattern == "attn":
        # full-attention archs run long context via a sliding-window variant
        cfg = cfg.replace(sliding_window=WINDOW_FOR_LONG)
    if shape_name == "long_500k" and cfg.pattern == "jamba":
        # jamba's sparse attention layers use a window; mamba layers are O(1)
        cfg = cfg.replace(sliding_window=WINDOW_FOR_LONG)
    return cfg


def optimize_config(cfg, *, multi_pod: bool, model_axis: int = 16):
    """Beyond-paper perf variant (EXPERIMENTS.md SSPerf): bf16 attention
    matmuls everywhere; sequence-parallel attention when head counts don't
    divide the model axis; expert-parallel MoE when expert counts do."""
    kw = dict(attn_bf16=True,
              dp_axes=("pod", "data") if multi_pod else ("data",))
    # sequence-parallel attention pays off only when the score all-reduces
    # GSPMD would otherwise emit are huge (wide models with head counts not
    # divisible by the model axis). For small-d models the per-layer
    # reshards cost more than they save (granite: 2.6x regression — SSPerf).
    if (cfg.num_heads % model_axis or cfg.num_kv_heads % model_axis) \
            and cfg.d_model >= 4096:
        kw["seq_shard_attn"] = True
        # per-device scores [B,KV,G,Sq/16,block] must fit alongside params
        kw["attn_block_kv"] = 256
    # expert-parallel pays when experts are FINE-GRAINED: the all-to-all of
    # dispatched activations replaces expert-weight gathers, a win only when
    # weights are large relative to per-token activations (deepseek 1408-dim
    # experts: 2.3x; jamba 24576-dim experts: 1.8x REGRESSION — SSPerf).
    if cfg.moe and cfg.num_experts % model_axis == 0 and cfg.moe_d_ff <= 4096:
        kw["expert_parallel"] = True
    return cfg.replace(**kw)


def _token_batch_shapes(cfg, C, b, S, *, stacked: bool):
    """ShapeDtypeStructs for one client-stacked token batch."""
    lead = (C, b) if stacked else (b,)
    S_text = S - cfg.num_image_tokens if cfg.vlm else S
    d = {
        "tokens": jax.ShapeDtypeStruct(lead + (S_text,), jnp.int32),
        "labels": jax.ShapeDtypeStruct(lead + (S_text,), jnp.int32),
        "mask": jax.ShapeDtypeStruct(lead + (S_text,), jnp.float32),
    }
    if cfg.vlm:
        d["image_embeds"] = jax.ShapeDtypeStruct(
            lead + (cfg.num_image_tokens, cfg.d_model), cfg.cdtype)
    if cfg.encdec:
        d["frames"] = jax.ShapeDtypeStruct(
            lead + (cfg.num_frames, cfg.d_model), cfg.cdtype)
    return d


def build_train(cfg, shape, mesh, fed=DRYRUN_FED):
    model = get_model(cfg)
    fsdp = sharded.needs_fsdp(cfg)
    dp = dp_axes(mesh)
    dpsize = int(np.prod([mesh.shape[a] for a in dp]))
    B, S = shape.global_batch, shape.seq_len

    # temporal: cohort scanned, inner batch sharded over dp;
    # spatial: clients = dp shards
    C = TEMPORAL_COHORT if fsdp else dpsize
    b = B // C

    clients = _token_batch_shapes(cfg, C, b, S, stacked=True)
    server = _token_batch_shapes(cfg, None, min(b, 8) * 1, S, stacked=False)
    batch_shapes = {
        "clients": clients,
        "server": server,
        "priority_mask": jax.ShapeDtypeStruct((C,), jnp.float32),
        "weights": jax.ShapeDtypeStruct((C,), jnp.float32),
    }

    batch_specs = round_batch_specs(batch_shapes, mesh, fsdp=fsdp)

    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_specs = auto_param_specs(param_shapes, mesh, fsdp=fsdp,
                                   expert_parallel=cfg.expert_parallel)
    # the round input/output is the full FederationState: params keep their
    # auto specs, optimizer moments inherit them, client-state replicates
    from repro.fl import engine
    state_shapes = jax.eval_shape(
        lambda p: engine.init_state(p, fed, C), param_shapes)
    state_specs = federation_state_specs(fed, param_specs)

    step = sharded.make_round_step(model, fed, C, fsdp=fsdp)
    args = (shaped_with(state_shapes, state_specs, mesh),
            shaped_with(batch_shapes, batch_specs, mesh))
    in_shardings = (jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs),
                    jax.tree.map(lambda s: NamedSharding(mesh, s), batch_specs))
    out_shardings = (jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs),
                     None)
    meta = {"mode": "train", "clients": C, "per_client_batch": b,
            "fsdp": fsdp, "local_steps": fed.local_epochs,
            "server_opt": fed.server_opt, "aggregator": fed.aggregator}
    return step, args, in_shardings, out_shardings, meta, param_shapes


def build_prefill(cfg, shape, mesh):
    model = get_model(cfg)
    fsdp = sharded.needs_fsdp(cfg)
    B, S = shape.global_batch, shape.seq_len
    batch_shapes = _token_batch_shapes(cfg, None, B, S, stacked=False)
    batch_specs = auto_batch_specs(batch_shapes, mesh)
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_specs = auto_param_specs(param_shapes, mesh, fsdp=fsdp,
                                   expert_parallel=cfg.expert_parallel)
    step = sharded.make_prefill_step(model)
    args = (shaped_with(param_shapes, param_specs, mesh),
            shaped_with(batch_shapes, batch_specs, mesh))
    in_shardings = (jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs),
                    jax.tree.map(lambda s: NamedSharding(mesh, s), batch_specs))
    # output KV caches must be sharded too, or each device materializes the
    # full [layers, B, S, KV, hd] cache (llava: 16GB/device unsharded)
    with mesh:      # seq_shard_attn constraints need an ambient mesh
        out_shapes = jax.eval_shape(step, *args)
    cache_specs = auto_tree_specs(out_shapes[0], mesh, model_dim_order="last")
    dp = dp_axes(mesh)
    dpsize = int(np.prod([mesh.shape[a] for a in dp]))
    logit_spec = P(dp, None) if B % dpsize == 0 and B >= dpsize else P(None, None)
    out_shardings = (jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs),
                     NamedSharding(mesh, logit_spec))
    meta = {"mode": "prefill", "batch": B, "seq": S, "fsdp": fsdp}
    return step, args, in_shardings, out_shardings, meta, param_shapes


def build_decode(cfg, shape, mesh):
    model = get_model(cfg)
    fsdp = sharded.needs_fsdp(cfg)
    B, S = shape.global_batch, shape.seq_len
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_specs = auto_param_specs(param_shapes, mesh, fsdp=fsdp,
                                   expert_parallel=cfg.expert_parallel)
    cache_shapes = jax.eval_shape(lambda: model.make_cache(B, S))
    cache_specs = auto_tree_specs(cache_shapes, mesh)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    dp = dp_axes(mesh)
    dpsize = int(np.prod([mesh.shape[a] for a in dp]))
    tok_spec = P(dp, None) if B % dpsize == 0 and B >= dpsize else P(None, None)

    step = sharded.make_serve_step(model)
    args = (shaped_with(param_shapes, param_specs, mesh),
            shaped_with(cache_shapes, cache_specs, mesh),
            jax.ShapeDtypeStruct(tok.shape, tok.dtype, sharding=NamedSharding(mesh, tok_spec)),
            jax.ShapeDtypeStruct(pos.shape, pos.dtype, sharding=NamedSharding(mesh, P())))
    in_shardings = (jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs),
                    jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs),
                    NamedSharding(mesh, tok_spec), NamedSharding(mesh, P()))
    out_shardings = (None, jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs))
    meta = {"mode": "decode", "batch": B, "cache_len": S, "fsdp": fsdp,
            "window": cfg.sliding_window}
    return step, args, in_shardings, out_shardings, meta, param_shapes


BUILDERS = {"train": build_train, "prefill": build_prefill, "decode": build_decode}

COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b")
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the (per-device) HLO."""
    out = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        shapes_blob, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(shapes_blob):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        out[op] = out.get(op, 0) + nbytes
    return out


def run_one(arch: str, shape_name: str, *, multi_pod: bool, fed=DRYRUN_FED,
            variant: str = "baseline", cfg_overrides: dict | None = None):
    shape = INPUT_SHAPES[shape_name]
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
    cfg = adapt_config(get_config(arch), shape_name)
    if variant == "opt":
        cfg = optimize_config(cfg, multi_pod=multi_pod)
        fed = fed.replace(agg_dtype="bfloat16")   # bf16 deltas on the wire
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    builder = BUILDERS[shape.kind]
    t0 = time.time()
    build = (builder(cfg, shape, mesh, fed) if shape.kind == "train"
             else builder(cfg, shape, mesh))
    step, args, in_sh, out_sh, meta, param_shapes = build

    with mesh:
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):          # jax < 0.5 returned [dict]
        cost = cost[0] if cost else None
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)

    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "meta": meta, "variant": variant,
        "n_params": param_count(param_shapes),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops") if cost else None,
        "bytes_per_device": cost.get("bytes accessed") if cost else None,
        "collective_bytes_per_device": coll,
        "memory": _mem_dict(mem),
        "devices": int(np.prod(list(mesh.shape.values()))),
    }
    return rec, hlo_text


def _mem_dict(mem):
    if mem is None:
        return None
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=["baseline", "opt"])
    # every federation knob — async/aggregator/clock/failure/guard/codec/
    # pool — comes from the shared surface so this CLI can never drift
    # from the trainer's (tests/test_pool.py pins the two flag sets equal)
    add_fed_args(ap)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--dump-hlo", default=None, metavar="DIR",
                    help="write each lowered target's optimized HLO "
                         "(gzip, one file per combo) plus a .lintmeta.json "
                         "sidecar into DIR, so fedlint (scripts/fedlint.py "
                         "--hlo-dir DIR) and the roofline analyze the same "
                         "artifacts instead of re-lowering; default: the "
                         "HLO goes next to the records in --out")
    return ap


def main():
    # before the first backend use: jax fixes the device count then
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    args = build_parser().parse_args()

    # a default command line yields {} -> fed stays LITERALLY DRYRUN_FED,
    # so the lowered round is bit-identical to the pre-CLI-refactor one
    fed = DRYRUN_FED.replace(**fed_from_args(args))

    archs = ARCH_IDS if args.arch == "all" else [ALIASES.get(args.arch, args.arch)]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    hlo_dir = args.dump_hlo or args.out
    os.makedirs(hlo_dir, exist_ok=True)
    failures = []
    for a in archs:
        cfg_name = get_config(a).name
        for s in shapes:
            tag = f"{cfg_name}__{s}__{'multi' if args.multi_pod else 'single'}"
            if args.variant != "baseline":
                tag += f"__{args.variant}"
            if args.async_depth > 0:
                tag += f"__async{args.async_depth}"
                if args.async_mode != "fifo":
                    tag += f"__{args.async_mode}{args.min_lag}"
                if args.adaptive_staleness:
                    tag += "__adaptive"
            if args.aggregator != "mean":
                tag += f"__{args.aggregator}"
            if args.latency_mode != "none":
                tag += f"__clock-{args.latency_mode}"
                if args.round_deadline != float("inf"):
                    tag += f"-dl{args.round_deadline:g}"
            if args.failure_model != "none":
                tag += f"__{args.failure_model}"
            if args.divergence_guard:
                tag += "__guard"
            if args.wire_codec != "identity":
                tag += f"__codec-{args.wire_codec}"
                if not args.error_feedback:
                    tag += "-noef"
            if args.candidate_pool > 0:
                tag += f"__pool{args.candidate_pool}"
                if args.pool_weighting != "uniform":
                    tag += f"-{args.pool_weighting}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip-existing] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                out = run_one(cfg_name, s, multi_pod=args.multi_pod,
                              variant=args.variant, fed=fed)
                if isinstance(out, tuple):
                    rec, hlo_text = out
                    with gzip.open(os.path.join(hlo_dir, tag + ".hlo.txt.gz"),
                                   "wt") as hf:
                        hf.write(hlo_text)
                    if args.dump_hlo:
                        # sidecar the knobs fedlint's allowances key on, so
                        # lint_hlo_text over the dump needs no re-lowering
                        devices = rec.get("devices", 0)
                        lint_meta = {"tag": tag, "pod": True, "rounds": 1,
                                     "m_total": rec["n_params"],
                                     "devices": devices,
                                     "devices_per_pod":
                                         devices // 2 if args.multi_pod
                                         else devices,
                                     "aggregator": fed.aggregator,
                                     "wire_codec": fed.wire_codec,
                                     "agg_dtype": fed.agg_dtype}
                        with open(os.path.join(hlo_dir,
                                               tag + ".lintmeta.json"),
                                  "w") as mf:
                            json.dump(lint_meta, mf, indent=1)
                else:
                    rec = out
            except Exception as e:  # noqa: BLE001 — record failures, keep going
                rec = {"arch": cfg_name, "shape": s, "multi_pod": args.multi_pod,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            if rec["status"] == "error":
                failures.append((tag, rec["error"]))
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"  -> {rec['status']}"
                  + (f" compile={rec.get('compile_s')}s" if rec["status"] == "ok" else
                     f" {rec.get('reason', rec.get('error', ''))[:200]}"), flush=True)
    # a broken lowering must fail the process, not just leave an error
    # record on disk — CI was going green on status:error JSONs
    if failures:
        print(f"\n[dryrun] {len(failures)} target(s) FAILED to lower/compile:")
        for tag, err in failures:
            print(f"  FAIL {tag}: {err[:200]}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
