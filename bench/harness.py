"""One run of one cell: resolve it by name, set it up, measure a window of
FedALIGN rounds, check the first rounds against the reference, and read
the per-layer metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own under ``bench/``, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the model as it is run, and the name
  of its family;
- ``bench/families/<family>.py``: the model family, which maps the
  configuration's keys onto the program's ``ModelConfig`` and gives the
  program's parameter layout, the plain model loss and the model's counts;
- ``bench/traffic/<traffic>.json``: the federation and its rounds;
- ``bench/metrics/<metric>.py``: a reader with ``read(ctx)``;
- ``bench/limits/<cell>.json``: the limits of the correctness check.

The window drives the program's own round: the step that
``fl.sharded.choose_round`` picks over ``fl.sharded.make_round_step``, fed
a donated ``FederationState`` round after round, with the per-round host
work of ``launch.train.run``'s loop: ``build_batches``, ``device_put`` with
the round's shardings, the step, a block on the state, and the read of the
stats. Load is a closed loop: a round starts when the last one returned.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import flops
import reference
from traffic.generator import RowDraws, federation

BENCH = os.path.dirname(os.path.abspath(__file__))
SET_UP_ROUNDS = 3


def log(msg):
    print(f"[bench] {msg}", flush=True)


# ------------------------------------------------------------ resolution
def _load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(root, workload):
    """The cell named ``workload`` with its files, from ``root`` (the
    directory that holds ``BENCHMARK.json``)."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    bench = os.path.join(root, spec["paths"][0])
    mc = _load_json(os.path.join(root, conf["file"]))

    def applies(metric):
        return (workload in metric["workloads"] if "workloads" in metric
                else True)

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {
        "name": workload, "cell": cell, "bench": bench, "mc": mc,
        "family": family_module(bench, mc, conf["file"]),
        "traffic": _load_json(os.path.join(bench, "traffic",
                                           cell["traffic"] + ".json")),
        "limits": _load_json(os.path.join(bench, "limits",
                                          workload + ".json")),
        "end_to_end": e2e, "per_layer": per_layer,
        "readers": {m["name"]: metric_reader(bench, m["name"])
                    for m in per_layer},
    }


def load_module(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(bench, mc, file):
    """``bench/families/<family>.py``, for the family the configuration
    file names; exits where it names none, or one that is not there."""
    here = sorted(f[:-3] for f in os.listdir(os.path.join(bench, "families"))
                  if f.endswith(".py"))
    name = mc.get("family")
    if name not in here:
        named = f"family {name!r}" if name else "no family"
        raise SystemExit(f"{file} names {named}; the families in "
                         f"bench/families/ are {here}")
    return load_module(f"family_{name}",
                       os.path.join(bench, "families", name + ".py"))


def metric_reader(bench, name):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return load_module(f"metric_{name}",
                       os.path.join(bench, "metrics", name + ".py")).read


def peaks_for(bench, device_kind):
    table = _load_json(os.path.join(bench, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in the peaks "
                         f"table ({sorted(table)})")
    return table[device_kind]


def chip_devices(res):
    """The cell's chips, with the compile cache on; exits (no result) where
    JAX finds no TPU, fewer chips than the cell asks for, or a device kind
    that the peaks table does not hold."""
    from repro.utils import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    chips = res["cell"]["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    peaks_for(res["bench"], devices[0].device_kind)
    return devices[:chips]


def model_config(family, mc):
    """The program's ModelConfig for the configuration file, every key
    that the family maps set from it."""
    from repro.configs import get_config
    family.require(mc)
    return get_config(mc["arch"]).replace(
        **{field: mc[key] for field, key in family.KEYS.items()})


def fed_config(traffic):
    from repro.configs.base import FedConfig
    return FedConfig(num_clients=traffic["clients"],
                     num_priority=traffic["priority"],
                     local_epochs=traffic["local_steps"],
                     epsilon=traffic["epsilon"], lr=traffic["lr"],
                     aggregator=traffic["aggregator"],
                     wire_codec=traffic["wire_codec"])


# --------------------------------------------------------------- session
class Session:
    """The compiled round and its state, driven round after round."""

    def __init__(self, res, devices):
        from repro.fl import sharded
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import bytes_limit
        from repro.models import get_model
        self.res, self.devices = res, list(devices)
        self.mc, self.tr = res["mc"], res["traffic"]
        self.family = res["family"]
        self.cfg = model_config(self.family, self.mc)
        self.fed = fed_config(self.tr)
        self.model = get_model(self.cfg)
        self.mesh = make_host_mesh(devices=self.devices)
        self.C = self.tr["clients"]
        self._check_layout()
        # the batch shapes only: streams and rows are drawn per seed in start()
        self.fed_data = {
            "tokens": np.zeros((self.C, self.tr["pool_sequences"],
                                self.tr["seq"] + 1), np.int32),
            "test_tokens": np.zeros((64, self.tr["seq"] + 1), np.int32),
            "priority_mask": np.arange(self.C) < self.tr["priority"],
            "weights": np.ones(self.C, np.float32)}
        self.batch_shapes = jax.eval_shape(lambda b: b,
                                           self._build(RowDraws(0)))
        t0 = time.perf_counter()
        self.fsdp, self.compiled = sharded.choose_round(
            self.cfg, self._compile, bytes_limit(self.devices[0]))
        self.step = self.compiled
        self.compile_s = time.perf_counter() - t0
        self.mode = "temporal" if self.fsdp else "spatial"
        self.state_sh, self.batch_sh = self._shardings(self.fsdp)
        self.init = self._make_init()
        self.norms = check.change_norms_fn(self.family, self.mc)
        self.state = None

    def _check_layout(self):
        """The reference's weights have the program's layout."""
        prog = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        ours = jax.eval_shape(reference.make_init(self.family, self.mc),
                              jax.random.PRNGKey(0))
        if (jax.tree.structure(prog) != jax.tree.structure(ours)
                or jax.tree.leaves(prog) != jax.tree.leaves(ours)):
            raise SystemExit("the configuration's weights do not have the "
                             "program's layout")
        self.param_shapes = prog

    def _build(self, draws):
        from repro.launch.train import build_batches
        return build_batches(self.cfg, self.fed_data, clients=self.C,
                             per_client=self.tr["per_client"],
                             seq=self.tr["seq"], rng=draws)

    def _shardings(self, fsdp):
        from jax.sharding import NamedSharding
        from repro.sharding.specs import (auto_param_specs,
                                          federation_state_specs,
                                          round_batch_specs)
        named = lambda specs: jax.tree.map(                     # noqa: E731
            lambda s: NamedSharding(self.mesh, s), specs)
        state = named(federation_state_specs(self.fed, auto_param_specs(
            self.param_shapes, self.mesh, fsdp=fsdp,
            expert_parallel=self.cfg.expert_parallel)))
        return state, named(round_batch_specs(self.batch_shapes, self.mesh,
                                              fsdp=fsdp))

    def _state_shapes(self):
        from repro.fl import engine
        return jax.eval_shape(lambda k: engine.init_state(
            self.model.init(k), self.fed, self.C),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def _compile(self, fsdp):
        from repro.fl import sharded
        state_sh, batch_sh = self._shardings(fsdp)
        abstract = lambda tree, sh: jax.tree.map(               # noqa: E731
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)
        step = jax.jit(sharded.make_round_step(self.model, self.fed, self.C,
                                               fsdp=fsdp),
                       out_shardings=(state_sh, None), donate_argnums=0)
        t0, outcome = time.perf_counter(), "failed to compile"
        try:
            with jax.set_mesh(self.mesh):
                compiled = step.lower(abstract(self._state_shapes(), state_sh),
                                      abstract(self.batch_shapes, batch_sh),
                                      jax.ShapeDtypeStruct((), jnp.int32)
                                      ).compile()
            outcome = "compiled"
            return compiled
        finally:
            log(f"{'temporal' if fsdp else 'spatial'} round {outcome} in "
                f"{time.perf_counter() - t0:.2f}s")

    def _make_init(self):
        from repro.fl import engine
        fn = lambda key: engine.init_state(                      # noqa: E731
            reference.init_params(self.family, self.mc, key), self.fed,
            self.C)
        return jax.jit(fn, out_shardings=self.state_sh)

    # ------------------------------------------------------------ rounds
    def start(self, seed):
        """Weights and rows from the seed, then the first rounds through the
        window's own call and feed; returns their observations."""
        self.seed = seed
        self.key = reference.seed_key(seed)
        self.fed_data = federation(self.tr, self.mc["vocab_size"], seed)
        self.draws = RowDraws(seed)
        self.state = None
        gc.collect()
        self.state = self.init(self.key)
        self.round_idx = 0
        obs = {"server_loss": [], "local_losses": [], "gates": []}
        for r in range(SET_UP_ROUNDS):
            rec = self.one_round()
            for k in ("server_loss", "local_losses", "gates"):
                obs[k].append(rec[k])
            if r == 0:
                obs["delta1"] = check.leaf_norms(
                    self.norms(self.key, self.state.params))
        obs["change3"] = check.leaf_norms(self.norms(self.key,
                                                     self.state.params))
        return obs

    def one_round(self):
        ann = jax.profiler.TraceAnnotation
        with ann("bench.build"):
            batch = self._build(self.draws)
        with ann("bench.put"):
            batch = jax.device_put(batch, self.batch_sh)
        with ann("bench.step"):
            self.state, stats = self.step(self.state, batch,
                                          jnp.int32(self.round_idx))
        with ann("bench.wait"):
            jax.block_until_ready(self.state)
        with ann("bench.read"):
            rec = {"server_loss": float(stats["server_loss"]),
                   "local_losses": np.asarray(stats["local_losses"],
                                              np.float64),
                   "gates": np.asarray(stats["gates"], np.float64)}
        rec["end"] = time.perf_counter()
        self.round_idx += 1
        return rec

    def window(self, seconds, max_rounds=None):
        """Rounds until ``seconds`` have passed (or ``max_rounds`` ran);
        returns (seconds elapsed, the rounds' records)."""
        rounds = []
        t0 = time.perf_counter()
        while True:
            rounds.append(self.one_round())
            if (rounds[-1]["end"] - t0 >= seconds
                    or (max_rounds and len(rounds) >= max_rounds)):
                break
        return rounds[-1]["end"] - t0, rounds

    def program_bytes(self):
        mem = self.compiled.memory_analysis()
        if mem is None:
            return None
        return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)

    def peak_bytes(self):
        """The fullest chip's peak: the allocator's peak in use, or the
        round program's own bytes where those are more (the allocator's
        counter leaves out the program's temporaries)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(max(peaks), self.program_bytes() or 0))

    def free(self):
        self.state = None
        gc.collect()

    def trained(self, gates):
        return int(np.sum(np.asarray(gates) > 0))

    # --------------------------------------------------------- reference
    def reference(self, precision="f32", follow=None, tol=0.0):
        """The reference's observations of the first rounds, on the first
        chip, from the same seed and rows. ``follow``: the gates of the
        observations it is compared with, taken where the reference's own
        loss gap lies within ``tol`` of eps (``Reference.round``)."""
        ref = reference.Reference(self.family, self.mc, self.tr, precision)
        with jax.default_device(self.devices[0]):
            params = ref.cast(reference.make_init(self.family,
                                                  self.mc)(self.key))
            obs = {"server_loss": [], "local_losses": [], "gates": [],
                   "margin": []}
            for r in range(SET_UP_ROUNDS):
                draws = (self.draws.log[2 * r], self.draws.log[2 * r + 1])
                tie = None if follow is None else (follow["gates"][r], tol)
                params, o = ref.round(params, self.fed_data, draws, tie)
                for k in o:
                    obs[k].append(o[k])
                if r == 0:
                    obs["delta1"] = check.leaf_norms(self.norms(self.key,
                                                                params))
            obs["change3"] = check.leaf_norms(self.norms(self.key, params))
        del params
        gc.collect()
        return obs


# ------------------------------------------------------------------ trace
def start_trace(path):
    shutil.rmtree(path, ignore_errors=True)
    jax.profiler.start_trace(path)


def stop_trace(path):
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {path}")
    return found[-1]


# -------------------------------------------------------------------- run
class _Compiles:
    """Counts compilations and persistent-cache loads inside a ``with``."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        self.n = 0

    def _event(self, name, *_args, **_kw):
        self.n += name in self.EVENTS

    def __enter__(self):
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._event)


def run(res, seed, seconds, trace, *, t_start, devices, trace_dir=None,
        fault=None):
    """One run of a resolved cell. ``fault`` (tests only) wraps the compiled
    step. Returns the result line's object."""
    tr = res["traffic"]
    sess = Session(res, devices)
    if fault is not None:
        sess.step = fault(sess, sess.step)
    log(f"round={sess.mode} compile_s={sess.compile_s:.2f} "
        f"program_bytes={sess.program_bytes()}")
    first = sess.start(seed)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f}")
    for r in range(SET_UP_ROUNDS):
        log(f"round {r}: server_loss={first['server_loss'][r]:.6f} local="
            f"{np.round(first['local_losses'][r], 6).tolist()} "
            f"gates={first['gates'][r].tolist()}")

    if trace:
        start_trace(trace_dir)
    with _Compiles() as compiles:
        elapsed, rounds = sess.window(seconds,
                                      tr["trace_rounds"] if trace else None)
    if trace:
        xplane = stop_trace(trace_dir)
    log(f"window: {len(rounds)} rounds in {elapsed:.3f}s, "
        f"compiles inside it: {compiles.n}; trained clients per round "
        f"{''.join(str(sess.trained(r['gates'])) for r in rounds)}; "
        f"server loss {rounds[0]['server_loss']:.4f} -> "
        f"{rounds[-1]['server_loss']:.4f}")
    peak = sess.peak_bytes()
    sess.free()

    ref_obs = sess.reference("f32", follow=first,
                             tol=res["limits"]["loss_gap"])
    numbers = check.readings(first, ref_obs, res["limits"]["loss_gap"])
    correct, checks = check.judge(numbers, res["limits"])
    failed = sum(1 for r in rounds
                 if not (np.isfinite(r["server_loss"])
                         and np.all(np.isfinite(r["local_losses"]))))
    correct = correct and failed == 0

    trained = [sess.trained(r["gates"]) for r in rounds]
    e2e = {
        "setup_s": setup_s,
        "round_s": elapsed / len(rounds),
        "trained_tokens_per_s": sum(trained) * tr["per_client"] * tr["seq"]
        * tr["local_steps"] / elapsed,
    }
    units = {m["name"]: m["unit"] for m in res["end_to_end"]}
    d0 = devices[0]
    result = {"correct": bool(correct), "attempted": len(rounds),
              "failed": failed, "metrics": {}, "device": {
                  "platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}}
    if not trace:
        result["metrics"] = {n: {"value": e2e[n], "unit": units[n]}
                             for n in units}
    else:
        tracemod = load_module("bench_trace", os.path.join(BENCH, "trace.py"))
        tr_data = tracemod.reduce(xplane, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"mc": res["mc"], "family": res["family"], "traffic": tr,
               "rounds": rounds, "trace": tr_data,
               "mode": sess.mode, "chips": len(devices),
               "program_bytes": sess.program_bytes(),
               "peaks": peaks_for(res["bench"], d0.device_kind)
               if d0.platform == "tpu" else None,
               "flops": flops,
               "kernel_seconds": tracemod.kernel_seconds}
        for m in res["per_layer"]:
            value = res["readers"][m["name"]](ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = tr_data["busy_s"]
        result["device"]["window_s"] = tr_data["window_s"]
        result["breakdown"] = {"device_ops": tr_data["top_ops"],
                               "idle_gaps": tr_data["idle_gaps"]}
    result["checks"] = checks
    return result
