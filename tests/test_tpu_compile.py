"""The main-path Pallas kernels compile for a real chip, at real widths.

Each test compiles for a described (not attached) TPU v5e: ``fedagg`` at
C = 4 clients x M = Qwen1.5-0.5B's parameter count, flash attention
forward and backward at B=4, S=512, H=KV=16, hd=64, at Phi-3-mini's
(2, 1024, 32, 96) with its 2047-token window, and at DeepSeek-V2-Lite's
latent attention (1, 4096, 16, 192), where the forward takes
``fwd_tile_plan``'s tiles and the backward ``bwd_tile_plan``'s (512 at
each) and both skip the ones above the diagonal; the grouped expert
matmul forward and backward at that model's expert shapes; and that
model's whole temporal round at the shapes of its benchmark cell, which
must fit the chip's memory; and an expert-parallel round of it on the 2x2
mesh, where each chip computes its own experts' products. Mosaic refuses what
interpret mode accepts (1-D blocks, in-kernel gathers, unaligned tiles,
too much VMEM), and the compiler refuses a program that does not fit the
chip's HBM, so these guard every change to the kernels without a chip.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.kernels.fedagg import fedagg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import get_model
from repro.utils import param_count

C = 4
ATTN = (4, 512, 16, 64)
PHI3_ATTN = (2, 1024, 32, 96)
MLA_ATTN = (1, 4096, 16, 192)
HBM = 16 * 2**30
BYTES_LIMIT = 16909336064       # a v5e's bytes_limit as its backend reports
# DeepSeek-V2-Lite as the benchmark cell runs it: layer 0 and 4 expert
# layers holding 8 of 64 experts, an eighth of the vocabulary; 4 clients
# of one 4096-token sequence, 2 local steps
DSV2_SEQ = 4096


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(scope="module")
def m_total():
    cfg = get_config("qwen1.5-0.5b")
    return param_count(jax.eval_shape(get_model(cfg).init,
                                      jax.random.PRNGKey(0)))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM
    return mem


@pytest.mark.parametrize("variant", ["mean", "int8", "dp", "trimmed_mean",
                                     "median"])
def test_fedagg_compiles_at_lm_width(one_chip, m_total, variant):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vec = s((C,))
    if variant == "int8":
        mem = _compile(lambda u, w, g, q: fedagg_pallas(
            u, w, g, codec="int8", dequant_scale=q),
            s((C, m_total), jnp.int8), vec, vec, vec)
    elif variant == "dp":
        mem = _compile(lambda u, w, g, r, n: fedagg_pallas(
            u, w, g, aggregator="dp", row_scale=r, noise=n, noise_scale=0.5),
            s((C, m_total)), vec, vec, vec, s((m_total,)))
    else:
        mem = _compile(lambda u, w, g: fedagg_pallas(
            u, w, g, aggregator=variant, trim_frac=0.25),
            s((C, m_total)), vec, vec)
    # ragged last tile is a partial block: no padded copy of [C, M_total]
    assert mem.temp_size_in_bytes < 2**20


@pytest.mark.parametrize("direction,shape,window", [
    ("fwd", ATTN, 0), ("bwd", ATTN, 0),
    ("fwd", PHI3_ATTN, 2047), ("bwd", PHI3_ATTN, 2047),
    ("fwd", MLA_ATTN, 0), ("bwd", MLA_ATTN, 0),
], ids=["fwd", "bwd", "fwd-phi3", "bwd-phi3", "fwd-mla", "bwd-mla"])
def test_flash_attention_compiles(one_chip, direction, shape, window):
    qkv = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)] * 3

    # latent attention's YaRN scale, 192**-0.5 * mscale(40, 0.707)**2
    scale = 0.1147213867929261 if shape == MLA_ATTN else None

    def attn(q, k, v):
        return flash_attention_pallas(q, k, v, window=window, scale=scale)

    if direction == "fwd":
        _compile(attn, *qkv)
    else:
        _compile(jax.grad(lambda q, k, v: attn(q, k, v)
                          .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
                 *qkv)


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate-up", "down"])
def test_grouped_matmul_compiles_at_expert_shapes(one_chip, k, n):
    """The held experts' products of one expert layer: 4096 tokens x 6
    slots of rows, 8 experts, forward and both gradients."""
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(lhs, rhs, sizes):
        y = kops.grouped_matmul(lhs, rhs, sizes, use_pallas=True)
        return jnp.sum(y.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1)), s((DSV2_SEQ * 6, k)),
             s((8, k, n)), s((8,), jnp.int32))


def test_deepseek_v2_lite_round_compiles_and_fits(one_chip, monkeypatch):
    """The temporal round of the cell, with the Pallas kernels the chip
    takes (flash at q/k width 192, the grouped expert matmul), within the
    chip's bytes_limit."""
    from repro.configs.base import FedConfig
    from repro.fl import engine, sharded
    monkeypatch.setattr(kops, "pallas_default", lambda: True)
    cfg = get_config("deepseek-v2-lite").replace(
        num_layers=5, num_experts=8, vocab_size=12800)
    model = get_model(cfg)
    fed = FedConfig(num_clients=C, num_priority=2, local_epochs=2,
                    epsilon=0.5, lr=0.002)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rows(*lead):
        return {"tokens": s((*lead, DSV2_SEQ), jnp.int32),
                "labels": s((*lead, DSV2_SEQ), jnp.int32),
                "mask": s((*lead, DSV2_SEQ), jnp.float32)}

    batch = {"clients": rows(C, 1), "server": rows(1),
             "priority_mask": s((C,), jnp.float32),
             "weights": s((C,), jnp.float32)}
    state = jax.tree.map(lambda x: s(x.shape, x.dtype), jax.eval_shape(
        lambda k: engine.init_state(model.init(k), fed, C),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    step = jax.jit(sharded.make_round_step(model, fed, C, fsdp=True),
                   donate_argnums=0)
    compiled = step.lower(state, batch,
                          jax.ShapeDtypeStruct((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "kernel.flash_fwd" in text and "kernel.moe_gmm" in text
    assert sharded._fits(compiled, BYTES_LIMIT)


def test_expert_parallel_round_computes_each_chips_own_experts(
        v5e_2x2, monkeypatch):
    """The spatial round of DeepSeek-V2-Lite (layer 0 and one expert layer
    of 8 held experts, 2 clients of 512 tokens) on a (data=2, model=2)
    mesh with the experts sharded over model: it fits a chip, and every
    grouped product on a chip takes 4 of the 8 experts and at most one
    client's rows, so no chip gathers the other chip's experts or the
    other client's tokens."""
    from repro.configs.base import FedConfig, InputShape
    from repro.launch.dryrun import build_train
    monkeypatch.setattr(kops, "pallas_default", lambda: True)
    mesh = Mesh(np.array(v5e_2x2).reshape(2, 2), ("data", "model"))
    cfg = get_config("deepseek-v2-lite").replace(
        num_layers=2, num_experts=8, vocab_size=12800, expert_parallel=True)
    fed = FedConfig(num_clients=2, num_priority=1, local_epochs=2,
                    epsilon=0.5, lr=0.002)
    step, args, in_sh, out_sh, meta, _ = build_train(
        cfg, InputShape("moe", 512, 2, "train"), mesh, fed)
    assert not meta["fsdp"] and meta["clients"] == 2
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=in_sh,
                           out_shardings=out_sh).lower(*args).compile()
    from repro.fl import sharded
    assert sharded._fits(compiled, BYTES_LIMIT)
    outs = [tuple(int(n) for n in m.group(1).split(","))
            for m in re.finditer(r"= bf16\[([\d,]+)\][^=]* custom-call\(.*"
                                 r"kernel\.moe_gmm", compiled.as_text())]
    weights = [o for o in outs if len(o) == 3]
    rows = [o[0] for o in outs if len(o) == 2]
    assert weights and {o[0] for o in weights} == {4}
    assert rows and max(rows) <= 512 * cfg.top_k
