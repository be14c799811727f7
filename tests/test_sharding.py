"""Auto-sharder rules on the production AbstractMesh (no devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.configs.base import FedConfig
from repro.models import get_model
from repro.sharding.specs import (auto_batch_specs, auto_param_specs,
                                  auto_tree_specs, federation_state_specs)


def _mesh(kind="single"):
    """The production mesh, described without devices."""
    if kind == "multi":
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _check_divisible(shapes, specs, mesh):
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(specs)[0]):
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % size == 0, (path, leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("kind", ["single", "multi"])
def test_param_specs_divisible_full_configs(arch, kind):
    mesh = _mesh(kind)
    cfg = get_config(arch)
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = auto_param_specs(shapes, mesh, fsdp=arch in
                             ("jamba_1_5_large_398b", "llava_next_34b"))
    _check_divisible(shapes, specs, mesh)


def test_model_axis_used_on_big_weights():
    cfg = get_config("qwen1_5_0_5b")
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = auto_param_specs(shapes, _mesh())
    # attention projections must be tensor-parallel
    wq_spec = specs["periods"]["l0"]["attn"]["wq"]
    assert "model" in tuple(wq_spec)
    # embed sharded too (vocab or d)
    assert any(x is not None for x in specs["embed"])


def test_fsdp_adds_data_axis():
    cfg = get_config("llava_next_34b")
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs_f = auto_param_specs(shapes, _mesh(), fsdp=True)
    leaves = jax.tree.leaves(jax.tree.map(
        lambda s: int("data" in [a for a in s if a]), specs_f,
        is_leaf=lambda s: isinstance(s, P)))
    assert sum(leaves) > 0


def test_batch_specs():
    shapes = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
              "odd": jax.ShapeDtypeStruct((3, 5), jnp.float32)}
    specs = auto_batch_specs(shapes, _mesh())
    assert specs["tokens"] == P(("data",), None) or specs["tokens"] == P(("data",),) \
        or specs["tokens"][0] == ("data",)
    assert all(s is None for s in specs["odd"])


def test_cache_specs_divisible():
    cfg = get_config("qwen2_5_3b")       # KV=2: model axis must NOT land on KV
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda: model.make_cache(128, 32768))
    specs = auto_tree_specs(shapes, _mesh())
    _check_divisible(shapes, specs, _mesh())


def test_cache_specs_batch_one():
    cfg = get_config("xlstm_125m")
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda: model.make_cache(1, 524288))
    specs = auto_tree_specs(shapes, _mesh())
    _check_divisible(shapes, specs, _mesh())


@pytest.mark.parametrize("server_opt,kw", [
    ("sgd", {}), ("momentum", {}), ("adam", {}), ("yogi", {}),
    ("momentum", {"server_momentum": 0.0}),     # collapses to stateless sgd
])
def test_federation_state_specs_match_state_tree(server_opt, kw):
    """The FederationState spec tree must mirror init_state's pytree for
    every optimizer layout (dryrun lowers the full state), with moments
    inheriting the param specs and client-state replicated."""
    from repro.fl import engine
    cfg = get_smoke("qwen1_5_0_5b")
    model = get_model(cfg)
    fed = FedConfig(server_opt=server_opt, **kw)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = auto_param_specs(shapes, _mesh())
    state_shapes = jax.eval_shape(lambda p: engine.init_state(p, fed, 8),
                                  shapes)
    sspecs = federation_state_specs(fed, pspecs)
    assert (jax.tree.structure(state_shapes) ==
            jax.tree.structure(sspecs, is_leaf=lambda s: isinstance(s, P)))
    assert sspecs.backlog == P() and sspecs.util_ema == P()
    if server_opt in ("adam", "yogi"):
        assert sspecs.opt_state["m"] == pspecs


def test_expert_parallel_toggle():
    cfg = get_config("jamba_1_5_large_398b")   # 16 experts == model axis
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sp = auto_param_specs(shapes, _mesh(), expert_parallel=True)
    moe_spec = sp["periods"]["l1"]["moe"]["w_gate"]
    # stacked periods axis + expert axis
    assert jax.tree.leaves(moe_spec)[0] is None or True
    flat = [a for a in moe_spec if a is not None]
    assert "model" in flat
    # expert dim (index 1 after the period-stack axis) carries model
    assert moe_spec[1] == "model"
