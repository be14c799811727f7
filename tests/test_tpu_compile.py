"""The main-path Pallas kernels compile for a real chip, at real widths.

Each test compiles for a described (not attached) TPU v5e: ``fedagg`` at
C = 4 clients x M = Qwen1.5-0.5B's parameter count, flash attention
forward and backward at B=4, S=512, H=KV=16, hd=64 and at Phi-3-mini's
(2, 1024, 32, 96) with its 2047-token window, where the forward takes
512 tiles and skips the ones above the diagonal. Mosaic refuses what
interpret mode accepts (1-D blocks, in-kernel gathers, unaligned tiles,
too much VMEM), and the compiler refuses a program that does not fit the
chip's HBM, so these guard every change to the kernels without a chip.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fedagg import fedagg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import get_model
from repro.utils import param_count

C = 4
ATTN = (4, 512, 16, 64)
PHI3_ATTN = (2, 1024, 32, 96)
HBM = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


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
], ids=["fwd", "bwd", "fwd-phi3", "bwd-phi3"])
def test_flash_attention_compiles(one_chip, direction, shape, window):
    qkv = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)] * 3

    def attn(q, k, v):
        return flash_attention_pallas(q, k, v, window=window)

    if direction == "fwd":
        _compile(attn, *qkv)
    else:
        _compile(jax.grad(lambda q, k, v: attn(q, k, v)
                          .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
                 *qkv)
