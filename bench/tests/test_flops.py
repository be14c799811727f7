"""bench/flops.py against the program's parameter count and hand counts."""
import glob
import itertools
import json
import os

import jax
import pytest

import flops
import harness

from conftest import BENCH, TINY_CONFIG

CONFIGS = sorted(glob.glob(os.path.join(BENCH, "configs", "*.json")))


@pytest.mark.parametrize("path", CONFIGS + ["tiny"],
                         ids=[os.path.basename(p) for p in CONFIGS] + ["tiny"])
def test_matmul_params_are_the_program_params_less_the_gather(path):
    mc = TINY_CONFIG if path == "tiny" else json.load(open(path))
    from repro.models import get_model
    from repro.utils import param_count
    model = get_model(harness.model_config(mc))
    n = param_count(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert flops.n_params(mc) == n
    assert flops.n_matmul(mc) == n - flops.n_gather(mc) - flops.n_vector(mc)


def test_attention_flops_match_a_hand_count():
    mc = dict(TINY_CONFIG, num_attention_heads=2, num_key_value_heads=2,
              head_dim=8, num_hidden_layers=3)
    B, S = 2, 5
    pairs = sum(1 for i, j in itertools.product(range(S), range(S)) if j <= i)
    # per pair, head and layer: q.k (hd MACs) and p*v (hd MACs), 2 FLOPs each
    hand = B * 3 * 2 * pairs * (2 * 8 + 2 * 8)
    assert flops.attn_fwd_flops(mc, B, S) == hand
    assert flops.attn_bwd_flops(mc, B, S) == 2 * hand


def test_attention_bytes_match_a_hand_count():
    mc = dict(TINY_CONFIG, num_attention_heads=4, num_key_value_heads=2,
              head_dim=8, num_hidden_layers=1)
    B, S = 1, 16
    q = B * S * 4 * 8 * 2                  # bf16 [B, S, H, hd]
    kv = B * S * 2 * 8 * 2                 # bf16 [B, S, KV, hd]
    row = B * 4 * S * 4                    # f32 [B, H, S]
    assert flops.attn_fwd_bytes(mc, B, S) == 2 * q + 2 * kv + row
    assert flops.attn_bwd_bytes(mc, B, S) == 4 * q + 4 * kv + 2 * row


def test_round_work_counts_trained_and_forward_tokens():
    mc, tr = TINY_CONFIG, {"clients": 4, "per_client": 2, "seq": 8,
                           "local_steps": 3}
    N = flops.n_matmul(mc)
    fwd = flops.attn_fwd_flops(mc, 2, 8)
    w = flops.round_work(mc, tr, trained=3, train_calls=4)
    assert w["trained_tokens"] == 3 * 3 * 2 * 8
    assert w["model_flops"] == (3 * 3 * 16 * 6 * N + 3 * 3 * 3 * fwd
                                + 5 * 16 * 2 * N + 5 * fwd)
    assert w["attn_flops"] == 5 * fwd + 4 * 3 * 3 * fwd
