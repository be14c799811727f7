"""Pod-scale round-step semantics on the single host device: spatial and
temporal engines must agree with each other, thread the same
FederationState, and train the model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.configs.base import FedConfig
from repro.fl import engine, sharded
from repro.launch.train import build_batches, run as train_run
from repro.data.tokens import make_token_federation
from repro.models import get_model

CFG = get_smoke("qwen1_5_0_5b").replace(remat=False)
MODEL = get_model(CFG)
FED = FedConfig(local_epochs=2, epsilon=1e9, lr=0.05)


def _batch(C=4, b=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    fd = make_token_federation(seed=seed, vocab=CFG.vocab_size, n_clients=C,
                               n_priority=2, seq_len=S,
                               tokens_per_client=(S + 1) * 8)
    return build_batches(CFG, fd, clients=C, per_client=b, seq=S, rng=rng)


def _state(fed, C=4, seed=0):
    return engine.init_state(MODEL.init(jax.random.PRNGKey(seed)), fed, C)


def test_spatial_round_trains():
    step = jax.jit(sharded.make_spatial_round(MODEL, FED, 4))
    state = _state(FED)
    batch = _batch()
    s1, t1 = step(state, batch)
    s2, t2 = step(s1, batch)
    assert float(t2["server_loss"]) < float(t1["server_loss"])
    assert np.all(np.asarray(t1["gates"]) == 1.0)      # eps = inf


def test_spatial_equals_temporal():
    """Same federation semantics whether clients are space- or
    time-multiplexed (weights equal => identical aggregation), including
    the carried state (backlog, EMAs)."""
    batch = _batch()
    state = _state(FED)
    ss, ts = jax.jit(sharded.make_spatial_round(MODEL, FED, 4))(state, batch)
    st, tt = jax.jit(sharded.make_temporal_round(MODEL, FED, 4))(state, batch)
    np.testing.assert_allclose(np.asarray(ts["local_losses"]),
                               np.asarray(tt["local_losses"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ss), jax.tree.leaves(st)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-5, rtol=5e-5)


def test_gating_excludes_misaligned():
    fed = FedConfig(local_epochs=1, epsilon=0.05, lr=0.05)
    step = jax.jit(sharded.make_spatial_round(MODEL, fed, 4))
    state = _state(fed)
    batch = _batch()
    # corrupt the last client's labels to force misalignment after warm start
    bad = jax.random.randint(jax.random.PRNGKey(9),
                             batch["clients"]["labels"][3:].shape, 0,
                             CFG.vocab_size)
    batch["clients"]["labels"] = batch["clients"]["labels"].at[3:].set(bad)
    # train until losses separate; the corrupted client must eventually
    # fall outside the eps band while priority gates stay 1
    excluded = False
    for _ in range(10):
        state, stats = step(state, batch)
        gates = np.asarray(stats["gates"])
        assert gates[0] == 1.0 and gates[1] == 1.0      # priority always
        if gates[3] == 0.0:
            excluded = True
            break
    assert excluded, np.asarray(stats["local_losses"])


def test_round_idx_drives_eps_schedule():
    """The sharded rounds follow the eps schedule instead of freezing it at
    t=0: a decaying eps admits everyone early and gates non-priority
    clients out in late rounds — on BOTH execution modes."""
    fed = FedConfig(local_epochs=1, epsilon=0.5, lr=0.05,
                    epsilon_schedule="exp", epsilon_decay=0.9)
    batch = _batch()
    state = _state(fed)
    for make in (sharded.make_spatial_round, sharded.make_temporal_round):
        step = jax.jit(make(MODEL, fed, 4))
        _, s0 = step(state, batch, jnp.int32(0))
        _, s9 = step(state, batch, jnp.int32(9))
        assert np.asarray(s0["gates"]).sum() == 4.0          # eps_0 = 0.5
        late = np.asarray(s9["gates"])                        # eps_9 ~ 2e-10
        assert np.all(late[:2] == 1.0)                        # priority kept
        assert late[2:].sum() == 0.0, late


def test_spatial_cohort_matches_dense_and_temporal():
    """Gather-train (max_cohort) spatial round and cond-skip temporal round
    both reproduce the dense spatial round, including when the eps schedule
    has gated clients out (cohort padding slots / skipped scan iterations)."""
    fed = FedConfig(local_epochs=2, epsilon=0.5, lr=0.05,
                    epsilon_schedule="exp", epsilon_decay=0.5)
    batch = _batch()
    state = _state(fed)
    for r in (0, 6):
        sd, td = jax.jit(sharded.make_spatial_round(MODEL, fed, 4))(
            state, batch, jnp.int32(r))
        sc, tc = jax.jit(sharded.make_spatial_round(
            MODEL, fed.replace(max_cohort=4), 4))(state, batch, jnp.int32(r))
        st, tt = jax.jit(sharded.make_temporal_round(MODEL, fed, 4))(
            state, batch, jnp.int32(r))
        np.testing.assert_array_equal(np.asarray(td["gates"]),
                                      np.asarray(tc["gates"]))
        np.testing.assert_array_equal(np.asarray(td["gates"]),
                                      np.asarray(tt["gates"]))
        for a, b in zip(jax.tree.leaves(sd), jax.tree.leaves(sc)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-5, rtol=5e-5)
        for a, b in zip(jax.tree.leaves(sd), jax.tree.leaves(st)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("server_opt", ["momentum", "adam", "yogi"])
def test_sharded_server_optimizers_thread_state(server_opt):
    """Two chained rounds with a stateful server optimizer: moments must
    advance (t counter / non-zero m) and spatial==temporal still holds."""
    fed = FED.replace(server_opt=server_opt, server_lr=0.5)
    batch = _batch()
    state = _state(fed)
    sp = jax.jit(sharded.make_spatial_round(MODEL, fed, 4))
    tp = jax.jit(sharded.make_temporal_round(MODEL, fed, 4))
    s1, _ = sp(state, batch, jnp.int32(0))
    s2, _ = sp(s1, batch, jnp.int32(1))
    if server_opt in ("adam", "yogi"):
        assert int(s2.opt_state["t"]) == 2
    m_norm = sum(float(jnp.sum(jnp.abs(l)))
                 for l in jax.tree.leaves(s2.opt_state["m"]))
    assert m_norm > 0.0
    t1, _ = tp(state, batch, jnp.int32(0))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(t1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-5, rtol=5e-5)


def test_spatial_cohort_overflow_keeps_best_matched():
    """K < #included: the spatial gather drops the worst loss-matched
    non-priority clients, reports the effective gates, and books the
    dropped client into the backlog ledger."""
    fed = FedConfig(local_epochs=1, epsilon=1e9, lr=0.05, max_cohort=3)
    step = jax.jit(sharded.make_spatial_round(MODEL, fed, 4))
    state, stats = step(_state(fed), _batch())
    gates = np.asarray(stats["gates"])
    assert gates.sum() == 3.0
    assert np.all(gates[:2] == 1.0)                           # priority kept
    # the surviving non-priority client is the better loss-matched one
    losses = np.asarray(stats["local_losses"])
    server = float(stats["server_loss"])
    kept, dropped = (2, 3) if gates[2] == 1.0 else (3, 2)
    assert abs(losses[kept] - server) <= abs(losses[dropped] - server)
    np.testing.assert_array_equal(
        np.asarray(state.backlog),
        np.asarray([0, 0, 0, 0]) + (np.arange(4) == dropped))


def test_temporal_grad_sim_streams_sketches():
    """The temporal (FSDP) round supports grad_sim via CountSketch scoring:
    its gates match the spatial round scored on the SAME sketches, and the
    aggregated params agree across the modes."""
    fed = FedConfig(local_epochs=1, epsilon=1e9, lr=0.05,
                    selection="grad_sim", sim_threshold=0.0,
                    grad_sim_sketch=True, sketch_dim=512)
    batch = _batch()
    state = _state(fed)
    st, tt = jax.jit(sharded.make_temporal_round(MODEL, fed, 4))(state, batch)
    ss, ts = jax.jit(sharded.make_spatial_round(MODEL, fed, 4))(state, batch)
    gates = np.asarray(tt["gates"])
    assert set(np.unique(gates)).issubset({0.0, 1.0})
    assert np.all(gates[:2] == 1.0)                           # priority in
    np.testing.assert_array_equal(gates, np.asarray(ts["gates"]))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(ss)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-5, rtol=5e-5)


def test_temporal_grad_sim_requires_sketch_opt_in():
    """Exact delta cosines don't exist for streamed clients: without the
    explicit grad_sim_sketch opt-in the temporal round refuses instead of
    silently gating differently from the spatial round."""
    fed = FedConfig(local_epochs=1, epsilon=1e9, selection="grad_sim")
    with pytest.raises(ValueError, match="grad_sim_sketch"):
        sharded.make_temporal_round(MODEL, fed, 4)


def test_pod_rounds_identity_codec_knobs_inert():
    """Both pod rounds under the identity wire: the codec-rate and
    error-feedback knobs must not perturb a single bit of the round (the
    codec-off branch is literally the legacy trace) and no ef_accum
    leaves join the state."""
    batch = _batch()
    state = _state(FED)
    knobbed = FED.replace(error_feedback=False, codec_topk_frac=0.5,
                          codec_sketch_dim=7)
    for make in (sharded.make_spatial_round, sharded.make_temporal_round):
        sa, ta = jax.jit(make(MODEL, FED, 4))(state, batch)
        sb, tb = jax.jit(make(MODEL, knobbed, 4))(state, batch)
        assert sa.ef_accum == () and sb.ef_accum == ()
        np.testing.assert_array_equal(np.asarray(ta["gates"]),
                                      np.asarray(tb["gates"]))
        for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pod_rounds_compressed_wire_ef_advances():
    """Both pod rounds run the int8 wire end to end: finite server loss
    and a non-zero EF accumulator after one round (the temporal round
    must switch to the gathered path — its streamed (num, den) mean carry
    never materializes the per-client rows a codec encodes)."""
    fed = FED.replace(wire_codec="int8")
    batch = _batch()
    state = _state(fed)
    for make in (sharded.make_spatial_round, sharded.make_temporal_round):
        s1, t1 = jax.jit(make(MODEL, fed, 4))(state, batch)
        assert np.isfinite(float(t1["server_loss"]))
        total = sum(float(jnp.sum(jnp.abs(e)))
                    for e in jax.tree.leaves(s1.ef_accum))
        assert total > 0.0


def test_sharded_cohort_select_is_engine_cohort_select():
    """The pod rounds must not grow their own gather copy: the overflow /
    backlog policy lives in engine.cohort_select ONLY."""
    import inspect
    src = inspect.getsource(sharded)
    assert "engine.cohort_select" in src
    assert "argsort" not in src and "lexsort" not in src


PHASE_VARIANTS = {
    "spatial_dense": (False, {}),
    "spatial_max_cohort": (False, {"max_cohort": 3}),
    "temporal_mean": (True, {}),
    "temporal_trimmed_mean": (True, {"aggregator": "trimmed_mean"}),
    "candidate_pool": (False, {"candidate_pool": 3}),
    "async_depth_1": (True, {"async_depth": 1, "backend": "scan_async"}),
}


@pytest.mark.parametrize("variant", list(PHASE_VARIANTS))
def test_round_phases_name_the_compiled_program(variant):
    """Every phase the round runs names some instruction of its compiled
    program (``fedalign.<phase>`` in the op_name metadata), and no
    instruction falls under two phases."""
    import re
    fsdp, knobs = PHASE_VARIANTS[variant]
    fed = FED.replace(epsilon=0.5, **knobs)
    step = jax.jit(sharded.make_round_step(MODEL, fed, 4, fsdp=fsdp))
    text = step.lower(_state(fed), _batch(), jnp.int32(0)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    seen = set()
    for name in op_names:
        phases = re.findall(r"fedalign\.(\w+)", name)
        assert len(phases) <= 1, name
        seen.update(phases)
    assert seen == set(sharded.PHASES)


def test_train_driver_end_to_end():
    params, hist = train_run(arch="qwen1.5-0.5b", smoke=True, rounds=3,
                             clients=4, n_priority=2, per_client=2, seq=32,
                             verbose=False)
    assert hist[-1]["server_loss"] < hist[0]["server_loss"] + 0.5


class _Compiled:
    """Stands in for a compiled round: only its ``memory_analysis``."""

    def __init__(self, args, temp, out=0, alias=0):
        from types import SimpleNamespace
        self.mem = SimpleNamespace(argument_size_in_bytes=args,
                                   temp_size_in_bytes=temp,
                                   output_size_in_bytes=out,
                                   alias_size_in_bytes=alias)

    def memory_analysis(self):
        return self.mem


@pytest.mark.parametrize("case", ["fits", "too_big", "compile_oom",
                                  "no_limit", "fsdp_arch"])
def test_round_choice_follows_device_memory(case):
    """``choose_round`` compiles the spatial round and keeps it where its
    program (args + temp + unaliased out) fits the device's limit; where
    it does not, or the compiler runs out of device memory, it compiles
    the temporal round. No reported limit (CPU) keeps the spatial round;
    the archs ``needs_fsdp`` names never try it."""
    from repro.configs import get_config
    cfg = get_config("qwen1.5-0.5b")
    limit = 16 * 10**9
    spatial = _Compiled(args=2 * 10**9, temp=6 * 10**9, out=2 * 10**9,
                        alias=2 * 10**9)
    if case == "too_big":
        spatial = _Compiled(args=2 * 10**9, temp=15 * 10**9)
    temporal = _Compiled(args=2 * 10**9, temp=8 * 10**9)
    tried = []

    def compile_round(fsdp):
        tried.append(fsdp)
        if not fsdp and case == "compile_oom":
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm.")
        return temporal if fsdp else spatial

    if case == "no_limit":
        limit = None
        spatial = _Compiled(args=2 * 10**9, temp=10**12)
    if case == "fsdp_arch":
        cfg = get_config("llava-next-34b")
    fsdp, compiled = sharded.choose_round(cfg, compile_round, limit)
    want = {"fits": [False], "no_limit": [False], "too_big": [False, True],
            "compile_oom": [False, True], "fsdp_arch": [True]}[case]
    assert tried == want
    assert fsdp is want[-1]
    assert compiled is (temporal if fsdp else spatial)


def test_round_choice_reraises_other_compile_errors():
    """Only running out of device memory moves the choice on; any other
    compile failure propagates."""
    from repro.configs import get_config

    def compile_round(fsdp):
        raise jax.errors.JaxRuntimeError("INVALID_ARGUMENT: bad program")

    with pytest.raises(jax.errors.JaxRuntimeError, match="INVALID_ARGUMENT"):
        sharded.choose_round(get_config("qwen1.5-0.5b"), compile_round,
                             16 * 10**9)


def test_train_driver_records():
    """The driver's history carries gates, timed seconds and (round 0)
    compile seconds and the round it chose: spatial on a CPU, which
    reports no memory limit."""
    _, hist = train_run(arch="qwen1.5-0.5b", smoke=True, rounds=2, clients=4,
                        n_priority=2, per_client=2, seq=32, verbose=False)
    assert hist[0]["compile_sec"] > 0 and "compile_sec" not in hist[1]
    assert hist[0]["round_mode"] == "spatial"
    for rec in hist:
        assert rec["sec"] > 0 and rec["gates"].shape == (4,)
        assert np.isfinite(rec["server_loss"])


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache is the checkout's fixed ``.jax_cache``."""
    import os
    from repro.utils import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


MESH_CHILD = r"""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops, ref
from repro.launch.mesh import make_host_mesh
from repro.launch.train import run
mesh = make_host_mesh()
assert mesh.shape == {"data": 4, "model": 1}
rows = NamedSharding(mesh, P("data"))


def on_mesh(fn, *args, batch_axes=("data",)):
    with jax.set_mesh(mesh), ops.kernels_per_shard(mesh, batch_axes):
        return jax.jit(fn)(*args)


# fedagg: the mean reduces per shard + one all-reduce, the order
# statistics gather the client axis
key = jax.random.PRNGKey(0)
u = jax.device_put(jax.random.normal(key, (4, 3000)), rows)
w, g = jnp.array([.1, .4, .3, .2]), jnp.array([1., 0., 1., 1.])
wants = {"mean": ref.fedagg_ref(u, w, g),
         "median": ref.fedagg_median_ref(u, w, g),
         "trimmed_mean": ref.fedagg_trimmed_ref(u, w, g, 0.25)}
for agg, want in wants.items():
    got = on_mesh(functools.partial(ops.fedagg, use_pallas=True,
                                    interpret=True, aggregator=agg,
                                    trim_frac=0.25), u, w, g)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

# a bf16 wire with two clients per shard: the partial means stay f32 and
# the mean is rounded to bf16 once, as on one device — both within half a
# bf16 ulp of the f32 mean
ub = (3 * jax.random.normal(jax.random.fold_in(key, 3), (8, 3000))
      ).astype(jnp.bfloat16)
w8 = jax.random.uniform(jax.random.fold_in(key, 4), (8,)) + 0.05
g8 = jnp.array([1., 1., 0., 1., 1., 1., 1., 0.])
exact = np.asarray(ref.fedagg_ref(ub.astype(jnp.float32), w8, g8))
half_ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(exact), 1e-30))) - 8)
single = ops.fedagg(ub, w8, g8, use_pallas=True, interpret=True)
sharded = on_mesh(functools.partial(ops.fedagg, use_pallas=True,
                                    interpret=True),
                  jax.device_put(ub, rows), w8, g8)
for got in (single, sharded):
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - exact)
    assert np.all(err <= half_ulp + 1e-6 * np.abs(exact)), err.max()

# flash attention, batch split over data
q, k = (jax.device_put(jax.random.normal(jax.random.fold_in(key, i),
                                         (4, 128, 4, 32)), rows)
        for i in (1, 2))
grads = lambda attn: jax.grad(lambda q, k, v: attn(q, k, v).sum(),
                              argnums=(0, 1, 2))
flash = functools.partial(ops.flash_attention, use_pallas=True,
                          interpret=True)
got = on_mesh(grads(flash), q, k, 0.5 * q)
for a, b in zip(got, jax.jit(grads(ref.attention_ref))(q, k, 0.5 * q)):
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

# flash attention inside a client vmap whose client axis maps over data:
# the kernel stays client-local, so no activation crosses devices
qc, kc = (jax.device_put(jax.random.normal(jax.random.fold_in(key, i),
                                           (4, 2, 128, 4, 32)), rows)
          for i in (5, 6))
local = jax.vmap(grads(flash), spmd_axis_name=("data",))
with jax.set_mesh(mesh), ops.kernels_per_shard(mesh):
    step = jax.jit(local).lower(qc, kc, 0.5 * qc).compile()
    got = step(qc, kc, 0.5 * qc)
hlo = step.as_text()
assert "all-to-all" not in hlo and "all-gather" not in hlo
for a, b in zip(got, jax.jit(jax.vmap(grads(ref.attention_ref)))(
        qc, kc, 0.5 * qc)):
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

# the spatial round through the trainer: four devices (one client each)
# against one device, same seed and batches
kw = dict(arch="qwen1.5-0.5b", smoke=True, rounds=2, clients=4,
          n_priority=2, per_client=2, seq=32, verbose=False)
p4, h4 = run(**kw, mesh=mesh)
p1, h1 = run(**kw, mesh=make_host_mesh(devices=jax.devices()[:1]))
assert h4[0]["round_mode"] == h1[0]["round_mode"] == "spatial"
for a, b in zip(h4, h1):
    np.testing.assert_array_equal(a["gates"], b["gates"])
    np.testing.assert_allclose(a["server_loss"], b["server_loss"], rtol=1e-5)
for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p1)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                               rtol=5e-5)
print("OK")
"""


def test_kernels_run_per_shard_on_a_multi_device_mesh():
    """XLA cannot partition a Mosaic kernel, so on a multi-device mesh the
    Pallas fedagg / flash attention run per shard under shard_map (the mean
    all-reduces f32 partial sums), client-local inside a client vmap; the
    spatial round on four devices matches one device. Four host devices
    need their own process; the kernels run in interpret mode there."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", MESH_CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]
