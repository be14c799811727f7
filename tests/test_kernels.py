"""Per-kernel allclose tests: Pallas (interpret=True) and the production jnp
paths, swept over shapes/dtypes, against the pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.fedagg import fedagg_pallas
from repro.kernels.flash_attention import (bwd_tile_plan,
                                           flash_attention_fwd_pallas,
                                           flash_attention_pallas,
                                           fwd_tile_plan)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype=jnp.float32, k=0):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape).astype(dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,Sq,Skv,H,KV,hd", [
    (1, 128, 128, 4, 4, 32),      # MHA
    (2, 128, 128, 8, 2, 64),      # GQA
    (1, 64, 256, 4, 1, 32),       # MQA, q shorter than kv
    (2, 256, 256, 6, 2, 16),      # odd head dim grouping
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 48])
def test_flash_attention(B, Sq, Skv, H, KV, hd, dtype, window):
    q = rand((B, Sq, H, hd), dtype, 1)
    k = rand((B, Skv, KV, hd), dtype, 2)
    v = rand((B, Skv, KV, hd), dtype, 3)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    got_jnp = ops.flash_attention(q, k, v, causal=True, window=window, block_kv=64)
    got_pal = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(got_pal, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_nondivisible_kv():
    """kv length not a block multiple (whisper's 1500 frames)."""
    q = rand((1, 96, 4, 32), k=1)
    k = rand((1, 96, 4, 32), k=2)
    v = rand((1, 96, 4, 32), k=3)
    want = ref.attention_ref(q, k, v, causal=False)
    got = ops.flash_attention(q, k, v, causal=False, block_kv=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Sq,Skv,H,KV,causal,window,tile", [
    (1024, 1024, 2, 1, True, 0, 512),      # GQA G=2, 3 of 4 tiles run
    (1024, 1024, 2, 1, True, 0, None),     # tiles from the plan (512)
    (1024, 1024, 2, 2, True, 300, 128),    # the window empties whole tiles
    (1024, 1024, 2, 2, True, 300, 512),
    (512, 1024, 2, 1, True, 300, 256),     # q_offset 512
    (256, 1024, 2, 2, True, 0, 256),
    (1024, 1024, 2, 2, False, 0, 512),     # nothing skipped
    (256, 1024, 2, 1, False, 200, None),   # keys behind the window skipped
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_tiles(Sq, Skv, H, KV, causal, window, tile, dtype):
    """The forward at 128/256/512 tiles, skipping the tiles the mask
    empties, against the full-scores oracle."""
    q = rand((1, Sq, H, 32), dtype, 1)
    k = rand((1, Skv, KV, 32), dtype, 2)
    v = rand((1, Skv, KV, 32), dtype, 3)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    got, _ = flash_attention_fwd_pallas(q, k, v, causal=causal, window=window,
                                        block_q=tile, block_kv=tile,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def _tiles_with_a_key(Sq, Skv, bq, bk, causal, window):
    """Tiles holding at least one unmasked (query, key) pair, from the
    oracle's dense mask."""
    q_pos = (Skv - Sq) + np.arange(Sq)[:, None]
    k_pos = np.arange(Skv)[None, :]
    mask = np.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    return int(mask.reshape(Sq // bq, bq, Skv // bk, bk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("Sq,Skv,kw,want", [
    (1024, 1024, {}, (512, 512, 3, 4)),
    (1024, 1024, dict(causal=False), (512, 512, 4, 4)),
    (384, 384, {}, (128, 128, 6, 9)),
    (768, 768, {}, (256, 256, 6, 9)),
    (64, 64, {}, (64, 64, 1, 1)),
    (1024, 1024, dict(window=2047), (512, 512, 3, 4)),
    (1024, 1024, dict(window=300, block_q=128, block_kv=128), (128, 128, 26, 64)),
    (512, 1024, dict(window=300, block_q=128, block_kv=128), (128, 128, 16, 32)),
    (256, 1024, dict(causal=False, window=200), (256, 512, 1, 2)),
    (1024, 1024, dict(block_q=64, block_kv=128), (64, 128, 72, 128)),
])
def test_fwd_tile_plan(Sq, Skv, kw, want):
    """Tiles from the lengths (explicit ones honoured); the tiles that run
    are exactly those the mask leaves a key in."""
    plan = fwd_tile_plan(Sq, Skv, **kw)
    assert plan == want
    bq, bk, run, _ = plan
    assert run == _tiles_with_a_key(Sq, Skv, bq, bk, kw.get("causal", True),
                                    kw.get("window", 0))


@pytest.mark.parametrize("Sq,Skv,hd,kw,want", [
    (1024, 1024, 96, dict(window=2047), (512, 512, 3, 4)),   # Phi-3 silo
    (512, 512, 64, {}, (512, 512, 1, 1)),                    # Qwen xdevice
    (4096, 4096, 192, {}, (512, 512, 36, 64)),               # DeepSeek silo8k
    (1024, 1024, 64, dict(causal=False), (512, 512, 4, 4)),
    (1024, 1024, 64, dict(window=300, block_q=128, block_kv=128),
     (128, 128, 26, 64)),
    (512, 1024, 64, dict(window=300), (512, 512, 2, 2)),
    (512, 1024, 64, dict(window=300, block_q=128, block_kv=128),
     (128, 128, 16, 32)),                                    # kv tile 0 unseen
    (256, 1024, 64, dict(causal=False, window=200), (256, 512, 1, 2)),
    (1024, 1024, 64, dict(block_q=64, block_kv=128), (64, 128, 72, 128)),
    (1024, 1024, 2048, {}, (256, 256, 10, 16)),              # wide heads
])
def test_bwd_tile_plan(Sq, Skv, hd, kw, want):
    """Tiles from the lengths and head width (explicit ones honoured); in
    each pass the tiles that run are exactly those the mask leaves a key
    in."""
    dq_plan, dkv_plan = bwd_tile_plan(Sq, Skv, hd, **kw)
    assert dq_plan == want
    for bq, bk, run, total in (dq_plan, dkv_plan):
        assert total == (Sq // bq) * (Skv // bk)
        assert run == _tiles_with_a_key(Sq, Skv, bq, bk,
                                        kw.get("causal", True),
                                        kw.get("window", 0))


# ------------------------------------------------------------ decode attention
@pytest.mark.parametrize("B,Skv,H,KV,hd", [
    (1, 256, 4, 4, 32), (3, 512, 8, 2, 64), (2, 128, 4, 1, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, Skv, H, KV, hd, dtype):
    q = rand((B, 1, H, hd), dtype, 4)
    kc = rand((B, Skv, KV, hd), dtype, 5)
    vc = rand((B, Skv, KV, hd), dtype, 6)
    kv_len = Skv - 37
    want = ref.decode_attention_ref(q, kc, vc, kv_len=kv_len)
    got_jnp = ops.decode_attention(q, kc, vc, kv_len=kv_len)
    got_pal = decode_attention_pallas(q, kc, vc, kv_len=kv_len,
                                      block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(got_pal, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# --------------------------------------------------------------------- fedagg
@pytest.mark.parametrize("C,M", [(4, 64), (16, 1000), (60, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedagg(C, M, dtype):
    u = rand((C, M), dtype, 7)
    w = jax.random.uniform(jax.random.fold_in(KEY, 8), (C,))
    g = (jax.random.uniform(jax.random.fold_in(KEY, 9), (C,)) > 0.4).astype(jnp.float32)
    g = g.at[0].set(1.0)                       # never empty
    want = ref.fedagg_ref(u, w, g)
    got_jnp = ops.fedagg(u, w, g)
    got_pal = fedagg_pallas(u, w, g, block_m=256, interpret=True)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(got_pal, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_fedagg_one_hot_returns_that_client():
    u = rand((5, 128), k=10)
    w = jnp.ones((5,))
    g = jnp.zeros((5,)).at[3].set(1.0)
    out = ops.fedagg(u, w, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(u[3]), atol=1e-6)


@pytest.mark.parametrize("C,M", [
    (1, 64),        # single client
    (1, 7),         # single client, M far below the lane width
    (4, 100),       # M not a lane multiple
    (5, 513),       # M just past a block boundary, C not a power of two
    (3, 2065),      # multi-block grid with a ragged tail
])
@pytest.mark.parametrize("agg", ["mean", "trimmed_mean", "median", "dp"])
def test_fedagg_shape_sweep_all_aggregators(C, M, agg):
    """fedagg_pallas (interpret) and the jnp lowering vs the naive refs on
    awkward shapes: M not a lane multiple, M < block_m, C == 1. Every
    registered in-kernel aggregator inherits the edge coverage."""
    u = rand((C, M), jnp.float32, k=C * 1009 + M)
    w = jax.random.uniform(jax.random.fold_in(KEY, C + M), (C,)) + 0.05
    g = (jax.random.uniform(jax.random.fold_in(KEY, C + M + 1), (C,)) > 0.3
         ).astype(jnp.float32)
    g = g.at[0].set(1.0)                       # never empty
    kw = {}
    if agg == "trimmed_mean":
        kw = dict(trim_frac=0.25)
        want = ref.fedagg_trimmed_ref(u, w, g, 0.25)
    elif agg == "median":
        want = ref.fedagg_median_ref(u, w, g)
    elif agg == "dp":
        norms = jnp.sqrt(jnp.sum(u.astype(jnp.float32) ** 2, axis=1))
        rs = jnp.minimum(1.0, 1.0 / jnp.maximum(norms, 1e-12))
        nz = jax.random.normal(jax.random.fold_in(KEY, C * 7 + M), (M,))
        kw = dict(row_scale=rs, noise=nz, noise_scale=0.7)
        want = ref.fedagg_dp_ref(u, w, g, rs, nz, 0.7)
    else:
        want = ref.fedagg_ref(u, w, g)
    got_jnp = ops.fedagg(u, w, g, aggregator=agg, **kw)
    got_pal = fedagg_pallas(u, w, g, block_m=256, interpret=True,
                            aggregator=agg, **kw)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_pal), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------- fedagg x wire codecs
CODEC_EDGE_SHAPES = [
    (1, 64),        # single client
    (1, 7),         # single client, M far below the lane width
    (4, 100),       # M not a lane multiple
    (65, 513),      # C past the 64-lane sublane tile, ragged M
    (3, 2065),      # multi-block grid with a ragged tail
]


def _codec_inputs(C, M, codec):
    """Encode a random [C, M] buffer (row C//2 forced all-zero — the int8
    scale-1.0 / topk zero-value / sketch empty-bucket edge) through the
    registry codec, returning (enc, codec_kw, decoded_ref)."""
    from repro.configs.base import FedConfig
    from repro.core.aggregation import get_wire_codec

    # sketch_dim < M forces hash collisions — a dim >= M sketch can be
    # lossless and the decode parity would not exercise the gather
    fed = FedConfig(codec_topk_frac=0.1, codec_sketch_dim=max(2, M // 3),
                    seed=3)
    u = rand((C, M), jnp.float32, k=C * 1013 + M)
    u = u.at[C // 2].set(0.0)
    cls = get_wire_codec(codec)
    enc, kw = cls.encode(fed, u)
    if codec == "int8":
        want_dec = ref.decode_int8_ref(enc, kw["dequant_scale"])
    elif codec == "topk":
        want_dec = ref.decode_topk_ref(enc, kw["topk_idx"], M)
    else:
        want_dec = ref.decode_sketch_ref(enc, kw["sketch_h"],
                                         kw["sketch_sign"])
    dec = cls.decode(fed, enc, kw, M)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want_dec),
                               atol=2e-5, rtol=2e-5)
    return enc, kw, want_dec


@pytest.mark.parametrize("C,M", CODEC_EDGE_SHAPES)
@pytest.mark.parametrize("codec", ["int8", "topk", "sketch"])
@pytest.mark.parametrize("agg", ["mean", "trimmed_mean", "median", "dp"])
def test_fedagg_codec_aggregator_sweep(C, M, codec, agg):
    """Every codec x aggregator pair: the fused decode-and-reduce (Pallas
    interpret and the jnp lowering) must match decode-then-reduce through
    the naive refs on the same edge shapes the dense sweep pins — plus an
    all-zero client row per case."""
    enc, codec_kw, dec = _codec_inputs(C, M, codec)
    w = jax.random.uniform(jax.random.fold_in(KEY, C * 7 + M), (C,)) + 0.05
    g = (jax.random.uniform(jax.random.fold_in(KEY, C * 7 + M + 1), (C,))
         > 0.3).astype(jnp.float32)
    g = g.at[0].set(1.0)                       # never empty
    g = g.at[C // 2].set(1.0)                  # the zero row is gated IN
    kw = {}
    if agg == "trimmed_mean":
        kw = dict(trim_frac=0.25)
        want = ref.fedagg_trimmed_ref(dec, w, g, 0.25)
    elif agg == "median":
        want = ref.fedagg_median_ref(dec, w, g)
    elif agg == "dp":
        norms = jnp.sqrt(jnp.sum(dec.astype(jnp.float32) ** 2, axis=1))
        rs = jnp.minimum(1.0, 1.0 / jnp.maximum(norms, 1e-12))
        nz = jax.random.normal(jax.random.fold_in(KEY, C * 11 + M), (M,))
        kw = dict(row_scale=rs, noise=nz, noise_scale=0.7)
        want = ref.fedagg_dp_ref(dec, w, g, rs, nz, 0.7)
    else:
        want = ref.fedagg_ref(dec, w, g)
    got_jnp = ops.fedagg(enc, w, g, aggregator=agg, **kw, **codec_kw)
    got_pal = fedagg_pallas(enc, w, g, block_m=256, interpret=True,
                            aggregator=agg, **kw, **codec_kw)
    assert got_jnp.dtype == jnp.float32 and got_pal.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got_pal), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_fedagg_codec_all_rows_zero():
    """An entirely-zero buffer through every codec still aggregates to
    exact zero (int8 scale floors at 1.0; sketch buckets are empty)."""
    C, M = 5, 130
    from repro.configs.base import FedConfig
    from repro.core.aggregation import get_wire_codec

    fed = FedConfig(codec_topk_frac=0.1, codec_sketch_dim=32, seed=3)
    u = jnp.zeros((C, M), jnp.float32)
    w = jnp.ones((C,))
    g = jnp.ones((C,))
    for codec in ("int8", "topk", "sketch"):
        enc, kw = get_wire_codec(codec).encode(fed, u)
        for out in (ops.fedagg(enc, w, g, **kw),
                    fedagg_pallas(enc, w, g, block_m=64, interpret=True,
                                  **kw)):
            np.testing.assert_array_equal(np.asarray(out),
                                          np.zeros((M,), np.float32))


def test_fedagg_pallas_refuses_codecs_without_a_tpu_kernel():
    """topk / sketch decode only in interpret mode; compiled (TPU) calls
    raise instead of quietly running the jnp lowering."""
    from repro.configs.base import FedConfig
    from repro.core.aggregation import get_wire_codec

    fed = FedConfig(codec_topk_frac=0.1, codec_sketch_dim=32, seed=3)
    u = rand((3, 200), k=31)
    w, g = jnp.ones((3,)), jnp.ones((3,))
    for codec in ("topk", "sketch"):
        enc, kw = get_wire_codec(codec).encode(fed, u)
        with pytest.raises(NotImplementedError, match=codec):
            fedagg_pallas(enc, w, g, **kw)


def test_fedagg_platform_default_is_the_jnp_lowering_off_tpu():
    """``use_pallas=None`` follows the platform: off a TPU no Pallas kernel
    is traced."""
    assert not ops.pallas_default()
    u = rand((3, 300), k=32)
    w, g = jnp.ones((3,)), jnp.ones((3,))
    jaxpr = jax.make_jaxpr(lambda u: ops.fedagg(u, w, g))(u)
    assert "pallas_call" not in str(jaxpr)


# -------------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("shape", [(4, 37, 128), (2, 256), (1, 5, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = rand(shape, dtype, 11)
    s = jax.random.uniform(jax.random.fold_in(KEY, 12), (shape[-1],))
    want = ref.rmsnorm_ref(x, s)
    got = rmsnorm_pallas(x, s, block_r=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# ------------------------------------------------------------------- ssm scan
@pytest.mark.parametrize("Bt,S,Di,N,chunk", [
    (1, 64, 16, 4, 16), (2, 128, 32, 8, 32), (2, 96, 8, 16, 32),
])
def test_ssm_scan(Bt, S, Di, N, chunk):
    x = rand((Bt, S, Di), k=13) * 0.5
    dt = jax.nn.softplus(rand((Bt, S, Di), k=14)) * 0.1
    A = -jnp.exp(rand((Di, N), k=15) * 0.5)
    B = rand((Bt, S, N), k=16)
    C = rand((Bt, S, N), k=17)
    D = rand((Di,), k=18)
    want = ref.ssm_scan_ref(x, dt, A, B, C, D)
    got_jnp = ops.ssm_scan(x, dt, A, B, C, D, chunk=chunk)
    got_pal = ssm_scan_pallas(x, dt, A, B, C, D, chunk=chunk,
                              block_d=max(Di // 2, 1), interpret=True)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(got_pal), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_ssm_step_matches_scan():
    """Sequential decode steps reproduce the chunked scan."""
    Bt, S, Di, N = 2, 16, 8, 4
    x = rand((Bt, S, Di), k=19) * 0.5
    dt = jax.nn.softplus(rand((Bt, S, Di), k=20)) * 0.1
    A = -jnp.exp(rand((Di, N), k=21) * 0.5)
    B = rand((Bt, S, N), k=22)
    C = rand((Bt, S, N), k=23)
    D = rand((Di,), k=24)
    want = ref.ssm_scan_ref(x, dt, A, B, C, D)
    h = jnp.zeros((Bt, Di, N))
    outs = []
    for t in range(S):
        h, y = ops.ssm_step(h, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        outs.append(y + x[:, t] * D[None])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-4)
