"""The MLA-MoE family (``bench/families/mla_moe.py``): the program's
DeepSeek-V2-Lite against the family's plain float32 reference, on seeded
weights at a CPU size, and the family's counts at the cell's size."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness
import reference
from traffic.generator import RowDraws, federation

from conftest import BENCH, ROOT, TINY_TRAFFIC, make_root

SEED = 2147483659
CELL = "deepseek-v2-lite.silo8k"
CONFIG = json.load(open(os.path.join(BENCH, "configs",
                                     "deepseek-v2-lite.json")))
# every mechanism of the cell's configuration at a CPU size: 1 dense and
# 2 expert layers, the layer holding 4 of the router's 8 experts, YaRN
# acting beyond 16 original positions, float32 compute
TINY = dict(CONFIG, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=32, n_routed_experts=4, router_experts=8,
            n_shared_experts=1, num_experts_per_tok=2, num_hidden_layers=3,
            vocab_size=256, compute_dtype="float32",
            yarn_original_max_position_embeddings=16,
            rope_scaling=dict(CONFIG["rope_scaling"],
                              original_max_position_embeddings=16))
TINY_LIMITS = {"loss_gap": 1e-3, "gates_diff": 0, "delta1_gap": 1e-2,
               "change3_gap": 1e-2}


def _family(mc):
    return harness.family_module(BENCH, mc, "config")


def _program(mc):
    from repro.models import get_model
    return get_model(harness.model_config(_family(mc), mc))


def test_layout_is_the_programs():
    fam = _family(TINY)
    prog = jax.eval_shape(_program(TINY).init, jax.random.PRNGKey(0))
    ours = jax.eval_shape(reference.make_init(fam, TINY),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(ours)
    assert jax.tree.leaves(prog) == jax.tree.leaves(ours)


def test_counts_at_the_cells_size():
    fam = _family(CONFIG)
    assert fam.n_matmul(CONFIG) == 257_949_696
    assert fam.n_params(CONFIG) == 535_060_992
    assert fam.n_vector(CONFIG) == 5 * (2 * 2048 + 512) + 2048
    # the embedding, and 8 - 0.75 experts of 3 x 2048 x 1408 a layer
    assert fam.n_gather(CONFIG) == 12800 * 2048 + 4 * 29 * 3 * 2048 * 1408 // 4
    assert fam.n_matmul(CONFIG) + fam.n_vector(CONFIG) \
        + fam.n_gather(CONFIG) == fam.n_params(CONFIG)
    assert fam.attention(CONFIG) == {"heads": 16, "kv_heads": 16,
                                     "d_qk": 192, "d_v": 128, "layers": 5}
    # 3072 held rows a layer for 4096 tokens: 6 slots a token, 8 of 64
    f, b = fam.expert_work(CONFIG, 4096)
    assert f == 4 * 6 * 3072 * 2048 * 1408
    assert b == 4 * 2 * (3 * 8 * 2048 * 1408 + 3072 * 3 * (2048 + 1408))
    assert fam.expert_work(CONFIG, 4096, backward=True) == (3 * f, 3 * b)


def test_require_refuses_what_the_program_does_not_compute():
    fam = _family(CONFIG)
    fam.require(CONFIG)
    for key, value in (("scoring_func", "sigmoid"), ("topk_method",
                       "group_limited_greedy"), ("q_lora_rank", 1536),
                       ("yarn_factor", 4)):
        with pytest.raises(SystemExit):
            fam.require(dict(CONFIG, **{key: value}))
    for key, value in (("beta_fast", 16), ("beta_slow", 2)):
        with pytest.raises(SystemExit):
            fam.require(dict(CONFIG, rope_scaling=dict(
                CONFIG["rope_scaling"], **{key: value})))


def _batch(mc, B=2, S=32):
    k = jax.random.PRNGKey(3)
    toks = jax.random.randint(k, (B, S + 1), 0, mc["vocab_size"])
    return reference.split_rows(np.asarray(toks))


def test_program_loss_and_gradients_match_the_reference():
    fam = _family(TINY)
    params = reference.make_init(fam, TINY)(reference.seed_key(SEED))
    batch = _batch(TINY)
    model = _program(TINY)
    with jax.default_matmul_precision("highest"):
        (lp, metrics), gp = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, batch)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p: fam.loss(TINY, "f32", p, batch)))(params)
    assert abs(float(lp) - float(lr)) < 1e-5
    assert 0 < int(metrics["moe_held_rows"]) <= 2 * 2 * 32 * 2
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6, err_msg=str(path))


def test_one_round_matches_the_reference(tmp_path):
    root = make_root(tmp_path, config=TINY, limits=TINY_LIMITS,
                     traffic=dict(TINY_TRAFFIC, per_client=1))
    res = harness.resolve(root, "tiny.cell")
    out = harness.run(res, SEED, 0.2, False, t_start=time.perf_counter(),
                      devices=jax.devices()[:1])
    assert out["correct"], out["checks"]
    assert out["checks"]["loss_gap"]["value"] < 1e-4


def test_the_readers_read_the_expert_scopes():
    fam = _family(CONFIG)
    tr = json.load(open(os.path.join(BENCH, "traffic", "silo8k.json")))
    ctx = {"mc": CONFIG, "family": fam, "traffic": tr,
           "rounds": [{"gates": [1.0, 1.0, 1.0, 0.0]}] * 2,
           "trace": {"busy_s": 2.0}, "chips": 1,
           "peaks": harness.peaks_for(BENCH, "TPU v5 lite"),
           "hlo": "unused"}
    import phases
    kernels = {"moe_route": 0.1, "moe_gmm": 0.4, "moe_combine": 0.1,
               "flash_fwd": 0.3}
    real = phases.round_phases
    phases.round_phases = lambda ctx: {"kernels": kernels}
    try:
        layer = harness.metric_reader(BENCH, "moe_layer_ms")(ctx)
        share = harness.metric_reader(BENCH, "moe_gmm_roofline")(ctx)
    finally:
        phases.round_phases = real
    assert layer == pytest.approx(300.0)
    tokens = tr["per_client"] * tr["seq"]
    f = 2 * (fam.expert_work(CONFIG, 5 * tokens)[0]
             + fam.expert_work(CONFIG, 6 * tokens, backward=True)[0])
    assert share == pytest.approx(100 * f / 197e12 / 0.4)


def test_the_readers_find_nothing_in_a_dense_program():
    fam = _family(CONFIG)
    ctx = {"mc": CONFIG, "family": fam, "traffic": {}, "rounds": [{}],
           "trace": {"busy_s": 1.0}, "chips": 1, "peaks": {}, "hlo": ""}
    for name in ("moe_layer_ms", "moe_gmm_roofline"):
        assert harness.metric_reader(BENCH, name)(ctx) is None
