"""Flash-attention backward Pallas kernels (two-pass dq / dk+dv) vs
jax.grad of the naive oracle, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention_fwd_pallas,
                                           flash_attention_pallas)


@pytest.mark.parametrize("B,Sq,H,KV,hd,window", [
    (1, 128, 4, 4, 32, 0),      # MHA
    (2, 128, 8, 2, 32, 0),      # GQA
    (1, 128, 4, 1, 32, 0),      # MQA
    (1, 128, 4, 2, 32, 48),     # sliding window
])
def test_flash_backward_matches_autodiff(B, Sq, H, KV, hd, window):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, Sq, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sq, KV, hd))

    def loss_pal(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=True, window=window,
                                   block_q=64, block_kv=64, interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, causal=True, window=window) ** 2)

    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4, err_msg=name)


def test_fwd_lse_matches_logsumexp():
    """The saved LSE must equal log-sum-exp of the masked scaled scores."""
    key = jax.random.PRNGKey(1)
    B, S, H, hd = 1, 64, 2, 16
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
    _, lse = flash_attention_fwd_pallas(q, k, v, causal=True, block_q=32,
                                        block_kv=32, interpret=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    want = jax.nn.logsumexp(s, axis=-1)          # [B,H,S]
    got = lse.reshape(B, H, 1, S)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("Sq,Skv,window,tile", [
    (1024, 1024, 0, 512),
    (1024, 1024, 300, 128),     # tiles behind the window skipped
    (512, 1024, 300, 512),      # q_offset 512
])
def test_fwd_lse_at_large_tiles(Sq, Skv, window, tile):
    """The LSE the backward reads, from the forward's large, skipping
    tiles, equals log-sum-exp of the masked scaled scores."""
    key = jax.random.PRNGKey(2)
    H, hd = 2, 32
    q = jax.random.normal(key, (1, Sq, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, Skv, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, Skv, H, hd))
    _, lse = flash_attention_fwd_pallas(q, k, v, causal=True, window=window,
                                        block_q=tile, block_kv=tile,
                                        interpret=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    q_pos = (Skv - Sq) + jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = (k_pos <= q_pos) & ((k_pos > q_pos - window) if window else True)
    s = jnp.where(mask[None, None], s, -1e30)
    want = jax.nn.logsumexp(s, axis=-1)          # [B,H,Sq]
    got = lse.reshape(1, H, 1, Sq)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("S,H,KV,window", [
    (1024, 2, 1, 0),            # both at 512 tiles, 3 of 4 run
    (1024, 2, 2, 300),
])
def test_flash_backward_after_planned_forward(S, H, KV, window):
    """Default tiles: the forward and the backward take their plans' (512
    here), and the backward reads the forward's LSE."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, S, H, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, KV, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, KV, 32))

    def loss_pal(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=True, window=window,
                                   interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, causal=True, window=window) ** 2)

    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize("Sq,Skv,H,KV,causal,window,tile", [
    (1024, 1024, 2, 2, True, 0, None),      # 3 of 4 tiles run, each pass
    (1024, 1024, 2, 2, True, 300, None),    # window
    (512, 1024, 2, 2, True, 300, None),     # keys no query sees
    (512, 1024, 2, 2, True, 300, 128),      # a kv tile no query sees
    (1024, 1024, 2, 2, False, 0, None),     # nothing skipped
    (1024, 1024, 4, 2, True, 0, None),      # GQA, G = 2
])
def test_flash_backward_at_planned_tiles(Sq, Skv, H, KV, causal, window,
                                         tile):
    """The backward at ``bwd_tile_plan``'s tiles (or the given ones),
    skipping the tiles the mask empties in both passes, against autodiff
    of the oracle; a key that no query sees gets exactly zero dk and dv."""
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (1, Sq, H, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, Skv, KV, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, Skv, KV, 32))

    def loss_pal(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                   block_q=tile, block_kv=tile,
                                   interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        o = ref.attention_ref(q, k, v, causal=causal, window=window)
        return jnp.sum(o ** 2)

    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4, err_msg=name)
    if window and Sq < Skv:
        # the first query (Skv - Sq) sees keys above Skv - Sq - window only
        unseen = Skv - Sq - window + 1
        for grad in gp[1:]:
            assert not np.asarray(grad[:, :unseen]).any()
