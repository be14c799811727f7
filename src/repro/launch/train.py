"""End-to-end FedALIGN training driver for the LM-scale architectures.

Runs real federated rounds of a (reduced or full) architecture on a
(data, model) mesh of the local devices — the same ``fl/sharded.py`` round
step the dry-run lowers for the production mesh. The round (spatial or
temporal) is chosen by compiling it against the device's memory, the
state is donated to the jitted round, and each round is timed to
``block_until_ready``.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --rounds 5 --seq 512                        # published widths
    PYTHONPATH=src python -m repro.launch.train --smoke --rounds 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs import get_config, get_smoke
from repro.configs.base import FedConfig
from repro.configs.cli import add_fed_args, fed_from_args
from repro.data.tokens import make_token_federation
from repro.fl import engine, sharded
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.sharding.specs import (auto_param_specs, federation_state_specs,
                                  round_batch_specs)
from repro.utils import enable_compile_cache, param_count


def device_line() -> str:
    d = jax.devices()
    return (f"platform={d[0].platform} kind={d[0].device_kind} "
            f"count={len(d)} jax={jax.__version__}")


def bytes_limit(device) -> int | None:
    """The device's memory limit as its backend reports it (None on CPU)."""
    stats = device.memory_stats()
    return None if not stats else stats.get("bytes_limit")


def _mode(fsdp: bool) -> str:
    return "temporal" if fsdp else "spatial"


def _abstract(tree, shardings):
    """ShapeDtypeStructs of ``tree`` carrying ``shardings`` (for .lower)."""
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                          sharding=s),
                        tree, shardings)


def build_batches(cfg, fed_data, *, clients, per_client, seq, rng):
    """Assemble one round's client-stacked token batch + server batch."""
    toks = fed_data["tokens"]                       # [C, n_seq, seq+1]
    C, n_seq, _ = toks.shape
    idx = rng.integers(0, n_seq, size=(clients, per_client))
    sel = np.stack([toks[c, idx[c]] for c in range(clients)])   # [C,b,seq+1]
    test = fed_data["test_tokens"]
    sidx = rng.integers(0, test.shape[0], size=(per_client,))
    server = test[sidx]

    def split(x):
        return {"tokens": jnp.asarray(x[..., :-1]),
                "labels": jnp.asarray(x[..., 1:]),
                "mask": jnp.ones(x[..., 1:].shape, jnp.float32)}

    return {
        "clients": split(sel),
        "server": split(server),
        "priority_mask": jnp.asarray(fed_data["priority_mask"], jnp.float32),
        "weights": jnp.asarray(fed_data["weights"]),
    }


def run(arch="qwen1.5-0.5b", smoke=False, rounds=10, clients=8, n_priority=4,
        per_client=4, seq=128, lr=0.05, epsilon=0.5, local_epochs=2,
        misalign_max=1.0, log_every=1, seed=0, verbose=True, mesh=None,
        **fed_kw):
    """Run ``rounds`` FedALIGN rounds; returns (params, history).

    ``mesh`` is the (data, model) mesh the round runs on (default: every
    local device, ``launch.mesh.make_host_mesh``). The round is chosen by
    compiling it against the device's memory (``sharded.choose_round``).
    Each history record holds the round's server loss, included
    non-priority count, gates and seconds to ``block_until_ready``; round
    0's also holds ``compile_sec`` (every compile the choice took) and
    ``round_mode`` (spatial | temporal).

    ``fed_kw`` passes any further FedConfig knob straight through —
    e.g. ``async_depth=2, staleness_decay=0.5, backend="scan_async"`` to
    drive the pod rounds with overlapped cohorts (plus
    ``async_mode="ready", min_lag=1`` for the FedBuff-style variable-lag
    buffer and ``adaptive_staleness=True`` for the drift-measured
    discount), or ``server_opt``."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    assert not cfg.encdec, "use examples/whisper for enc-dec training"
    model = get_model(cfg)
    fed = FedConfig(num_clients=clients, num_priority=n_priority,
                    local_epochs=local_epochs, epsilon=epsilon, lr=lr,
                    **fed_kw)
    fed_data = make_token_federation(seed=seed, vocab=cfg.vocab_size,
                                     n_clients=clients, n_priority=n_priority,
                                     seq_len=seq, misalign_max=misalign_max,
                                     tokens_per_client=max(8192, per_client * (seq + 1) * 4))
    # validate while still concrete — inside the jitted round they're tracers
    from repro.core.aggregation import check_client_weights
    check_client_weights(fed_data["weights"], where="federation weights")

    mesh = make_host_mesh() if mesh is None else mesh
    key = jax.random.PRNGKey(seed)
    param_shapes = jax.eval_shape(model.init, key)
    state_shapes = jax.eval_shape(
        lambda k: engine.init_state(model.init(k), fed, clients), key)
    rng = np.random.default_rng(seed)
    batch = build_batches(cfg, fed_data, clients=clients,
                          per_client=per_client, seq=seq, rng=rng)
    if verbose:
        print(f"[train] {device_line()}")
        print(f"[train] {cfg.name} params={param_count(param_shapes):,} "
              f"clients={clients} mesh={dict(mesh.shape)}")

    def named(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    # the whole cross-round carry (params + server-optimizer moments +
    # backlog + utility EMAs) threads through the driver as ONE pytree,
    # created in place on the mesh and donated to every round
    def shardings(fsdp):
        state_sh = named(federation_state_specs(fed, auto_param_specs(
            param_shapes, mesh, fsdp=fsdp,
            expert_parallel=cfg.expert_parallel)))
        return state_sh, named(round_batch_specs(batch, mesh, fsdp=fsdp))

    def compile_round(fsdp):
        state_sh, batch_sh = shardings(fsdp)
        step = jax.jit(sharded.make_round_step(model, fed, clients, fsdp=fsdp),
                       out_shardings=(state_sh, None), donate_argnums=0)
        t0, outcome = time.perf_counter(), "failed to compile"
        try:
            with jax.set_mesh(mesh):    # kernels shard_map over this mesh
                compiled = step.lower(_abstract(state_shapes, state_sh),
                                      _abstract(batch, batch_sh),
                                      jnp.int32(0)).compile()
            outcome = "compiled"
            return compiled
        finally:
            if verbose:
                print(f"[train] {_mode(fsdp)} round {outcome} in "
                      f"{time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    fsdp, step = sharded.choose_round(cfg, compile_round,
                                      bytes_limit(mesh.devices.flat[0]))
    compile_sec = time.perf_counter() - t0
    state_sh, batch_sh = shardings(fsdp)
    state = jax.jit(lambda k: engine.init_state(model.init(k), fed, clients),
                    out_shardings=state_sh)(key)
    mem = step.memory_analysis()
    if verbose:
        print(f"[train] round={_mode(fsdp)} (bytes_limit="
              f"{bytes_limit(mesh.devices.flat[0])})")
    if verbose and mem is not None:
        print(f"[train] round program bytes per device: "
              f"args={mem.argument_size_in_bytes} "
              f"out={mem.output_size_in_bytes} "
              f"temp={mem.temp_size_in_bytes} "
              f"aliased={mem.alias_size_in_bytes}")
    history = []
    halt_skips = int(fed.max_nonfinite_skips) if fed.divergence_guard else 0
    for r in range(rounds):
        if r > 0:
            batch = build_batches(cfg, fed_data, clients=clients,
                                  per_client=per_client, seq=seq, rng=rng)
        batch = jax.device_put(batch, batch_sh)
        t0 = time.perf_counter()
        state, stats = step(state, batch, jnp.int32(r))
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        rec = {"round": r,
               "server_loss": float(stats["server_loss"]),
               "included": float(jnp.sum(stats["gates"])) - n_priority,
               "gates": np.asarray(stats["gates"]),
               "theta_round": float(stats["theta_round"]),
               "sec": dt}
        if r == 0:
            rec["compile_sec"] = compile_sec
            rec["round_mode"] = _mode(fsdp)
        if "lost_clients" in stats:
            rec["lost_clients"] = float(stats["lost_clients"])
        if "skipped_nonfinite" in stats:
            rec["skipped_nonfinite"] = int(stats["skipped_nonfinite"])
        history.append(rec)
        if verbose and r % log_every == 0:
            print(f"  round {r:3d} server_loss={rec['server_loss']:.4f} "
                  f"included_nonpri={rec['included']:.0f} ({dt:.3f}s)")
        if halt_skips > 0 and rec.get("skipped_nonfinite", 0) >= halt_skips:
            print(f"[train] halting at round {r}: "
                  f"{rec['skipped_nonfinite']} consecutive non-finite "
                  f"aggregates (>= max_nonfinite_skips={halt_skips}); "
                  "params are the last finite ones")
            break
    from repro.core.aggregation import dp_report
    dp = dp_report(fed, len(history))
    if dp is not None and verbose:
        eps, delta = dp
        print(f"[train] DP budget spent: epsilon={eps:.3g} at "
              f"delta={delta:g} (z={fed.dp_noise}, "
              f"{len(history)} rounds, RDP accountant)")
    return state.params, history


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of the arch (default: published widths)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    # every federation knob — aggregator/clock/failure/guard/codec/async/
    # pool — comes from the shared surface so this CLI can never drift
    # from the dry-run's (tests/test_pool.py pins the two flag sets equal)
    add_fed_args(ap)
    return ap


def main():
    a = build_parser().parse_args()
    enable_compile_cache()
    run(arch=a.arch, smoke=a.smoke, rounds=a.rounds, clients=a.clients,
        seq=a.seq, lr=a.lr, **fed_from_args(a))


if __name__ == "__main__":
    main()
