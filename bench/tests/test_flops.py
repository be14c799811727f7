"""bench/flops.py and the families' counts against the program's parameter
count and hand counts."""
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


def _family(mc):
    return harness.family_module(BENCH, mc, "config")


@pytest.mark.parametrize("path", CONFIGS + ["tiny"],
                         ids=[os.path.basename(p) for p in CONFIGS] + ["tiny"])
def test_matmul_params_are_the_program_params_less_the_gather(path):
    mc = TINY_CONFIG if path == "tiny" else json.load(open(path))
    fam = _family(mc)
    from repro.models import get_model
    from repro.utils import param_count
    model = get_model(harness.model_config(fam, mc))
    n = param_count(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert fam.n_params(mc) == n
    assert fam.n_matmul(mc) == n - fam.n_gather(mc) - fam.n_vector(mc)


def test_attention_flops_match_a_hand_count():
    mc = dict(TINY_CONFIG, num_attention_heads=2, num_key_value_heads=2,
              head_dim=8, num_hidden_layers=3)
    att = _family(mc).attention(mc)
    B, S = 2, 5
    pairs = sum(1 for i, j in itertools.product(range(S), range(S)) if j <= i)
    # per pair, head and layer: q.k (hd MACs) and p*v (hd MACs), 2 FLOPs each
    hand = B * 3 * 2 * pairs * (2 * 8 + 2 * 8)
    assert flops.attn_fwd_flops(att, B, S) == hand
    assert flops.attn_bwd_flops(att, B, S) == 2 * hand
    # q/k wider than v, as latent attention has them: q.k at 12, p*v at 8
    wide = dict(att, d_qk=12)
    assert flops.attn_fwd_flops(wide, B, S) == B * 3 * 2 * pairs * (
        2 * 12 + 2 * 8)


def test_attention_bytes_match_a_hand_count():
    mc = dict(TINY_CONFIG, num_attention_heads=4, num_key_value_heads=2,
              head_dim=8, num_hidden_layers=1)
    att = _family(mc).attention(mc)
    B, S = 1, 16
    q = B * S * 4 * 8 * 2                  # bf16 [B, S, H, hd]
    kv = B * S * 2 * 8 * 2                 # bf16 [B, S, KV, hd]
    row = B * 4 * S * 4                    # f32 [B, H, S]
    assert flops.attn_fwd_bytes(att, B, S) == 2 * q + 2 * kv + row
    assert flops.attn_bwd_bytes(att, B, S) == 4 * q + 4 * kv + 2 * row
    # q and k at the q/k width 12, v and the output at the v width 8
    wide = dict(att, d_qk=12)
    q12, kv12 = B * S * 4 * 12 * 2, B * S * 2 * 12 * 2
    assert flops.attn_fwd_bytes(wide, B, S) == q12 + q + kv12 + kv + row
    assert flops.attn_bwd_bytes(wide, B, S) == (2 * q12 + 2 * q + 2 * kv12
                                                + 2 * kv + 2 * row)


def test_round_work_counts_trained_and_forward_tokens():
    mc, tr = TINY_CONFIG, {"clients": 4, "per_client": 2, "seq": 8,
                           "local_steps": 3}
    fam = _family(mc)
    N = fam.n_matmul(mc)
    fwd = flops.attn_fwd_flops(fam.attention(mc), 2, 8)
    w = flops.round_work(fam, mc, tr, trained=3, train_calls=4)
    assert w["trained_tokens"] == 3 * 3 * 2 * 8
    assert w["model_flops"] == (3 * 3 * 16 * 6 * N + 3 * 3 * 3 * fwd
                                + 5 * 16 * 2 * N + 5 * fwd)
    assert w["attn_flops"] == 5 * fwd + 4 * 3 * 3 * fwd
