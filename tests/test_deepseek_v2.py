"""DeepSeek-V2-Lite's mechanisms in the program, on seeded weights at a CPU
size: the dropless expert layer that holds a share of the router's
experts, unnormalised top-k weights, YaRN's frequencies and softmax scale,
the plain query projection of latent attention, and the grouped matmul's
Pallas kernel against its jnp path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.kernels import ops as kops
from repro.models import get_model
from repro.models.attention import init_mla, mla_attention_block, mla_rope_and_scale
from repro.models.layers import swiglu_apply
from repro.models.moe import init_moe, moe_apply

KEY = jax.random.PRNGKey(0)
# the cell's expert layer at small widths: a router of 64, top-6, 2 shared
LAYER = get_smoke("deepseek-v2-lite").replace(
    d_model=32, moe_d_ff=16, num_experts=64, router_experts=64, top_k=6,
    num_shared_experts=2)


def _x(T=48, d=32, key=1):
    return jax.random.normal(jax.random.PRNGKey(key), (1, T, d), jnp.float32)


def _plain_experts(p, x, cfg):
    """Every held expert on every token, weighted by its gate weight (0
    where the token is not routed to it), plus the shared experts."""
    xf = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(jnp.dot(xf, p["w_router"], precision="highest"))
    topw, tope = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    gate = jnp.sum(jnp.where(tope[..., None] == jnp.arange(cfg.router_width),
                             topw[..., None], 0.0), axis=1)[:, :cfg.num_experts]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["w_gate"])) \
        * jnp.einsum("td,edf->tef", xf, p["w_up"])
    y = jnp.einsum("tef,efd,te->td", h, p["w_down"], gate)
    if cfg.num_shared_experts:
        y = y + swiglu_apply(p["shared"], xf, jnp.float32)
    return y.reshape(x.shape)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of 8 experts each, their router the same 64 wide: the
    parts they compute, with the shared experts counted once, sum to the
    layer that holds all 64."""
    full = init_moe(KEY, LAYER)
    x = _x()
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_apply(full, x, LAYER)
        share_cfg = LAYER.replace(num_experts=8)
        shared = swiglu_apply(full["shared"], x, jnp.float32)
        total = shared
        for j in range(8):
            ex = slice(8 * j, 8 * j + 8)
            # chip j holds experts 8j..8j+7: numbered from 0 on the chip
            part = {"w_router": jnp.roll(full["w_router"], -8 * j, axis=1),
                    "w_gate": full["w_gate"][ex], "w_up": full["w_up"][ex],
                    "w_down": full["w_down"][ex], "shared": full["shared"]}
            y, aux = moe_apply(part, x, share_cfg)
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_a_router_on_one_expert_drops_nothing():
    """Every token's first slot on expert 0: eight times an even share,
    every slot to a held expert computed."""
    cfg = LAYER.replace(num_experts=8)
    p = init_moe(KEY, cfg)
    p["w_router"] = p["w_router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    x = jnp.abs(_x()) + 1.0
    with jax.default_matmul_precision("highest"):
        y, aux = moe_apply(p, x, cfg)
        want = _plain_experts(p, x, cfg)
    T = x.shape[1]
    probs = jax.nn.softmax(jnp.dot(x[0], p["w_router"], precision="highest"))
    held_slots = int(jnp.sum(jax.lax.top_k(probs, cfg.top_k)[1]
                             < cfg.num_experts))
    assert int(aux["held_rows"]) == held_slots >= T
    assert float(aux["load_ratio"]) > 2.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    model = get_model(get_smoke("deepseek-v2-lite"))
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "labels": jnp.zeros((1, 16), jnp.int32),
             "mask": jnp.ones((1, 16))}
    _, metrics = model.loss_fn(model.init(KEY), batch)
    # one token, layers 1 and 2: its 2 slots over the 4 held of 8 experts
    assert 0 <= int(metrics["moe_held_rows"]) <= 2 * 2 * 16


@pytest.mark.parametrize("norm", [False, True])
def test_layer_matches_the_plain_experts(norm):
    cfg = LAYER.replace(num_experts=8, norm_topk_prob=norm)
    p = init_moe(KEY, cfg)
    x = _x()
    with jax.default_matmul_precision("highest"):
        y, _ = moe_apply(p, x, cfg)
        want = _plain_experts(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_unnormalised_weights_are_the_normalised_times_their_sum():
    cfg = LAYER.replace(num_experts=8)
    p = init_moe(KEY, cfg)
    x = _x()
    with jax.default_matmul_precision("highest"):
        shared = swiglu_apply(p["shared"], x, jnp.float32)
        raw = moe_apply(p, x, cfg)[0] - shared
        normed = moe_apply(p, x, cfg.replace(norm_topk_prob=True))[0] - shared
        probs = jax.nn.softmax(jnp.dot(x[0], p["w_router"],
                                       precision="highest"))
    topsum = jnp.sum(jax.lax.top_k(probs, cfg.top_k)[0], -1)
    np.testing.assert_allclose(np.asarray(raw[0]),
                               np.asarray(normed[0] * topsum[:, None]),
                               rtol=1e-5, atol=1e-6)
    # a token's 6 weights of 64 sum to under 1: the two differ
    assert float(jnp.max(topsum)) < 0.99


def test_yarn_frequencies_and_scale_are_deepseeks():
    cfg = get_config("deepseek-v2-lite")
    freqs, scale = mla_rope_and_scale(cfg)
    assert scale == pytest.approx(0.114721386793, rel=1e-9)
    # dims 0-10 turn as the base rope, 23-31 at 1/40 of it, a ramp between
    want = {0: 1.0, 10: 0.05623413251903491, 11: 0.03900692656714386,
            16: 0.0055, 22: 0.0001778279410038922,
            23: 3.33380358040831e-05, 31: 3.3338035804083097e-06}
    assert freqs.shape == (32,)
    for i, v in want.items():
        assert float(freqs[i]) == pytest.approx(v, rel=1e-5), i
    # no YaRN: the plain rope and (q/k width)^-0.5
    assert mla_rope_and_scale(cfg.replace(yarn_factor=0.0)) == (None,
                                                                192 ** -0.5)


def test_plain_query_projection_matches_the_low_rank_one():
    """A low-rank query path whose first projection is orthogonal on
    unit-RMS rows is the plain projection by their product (the norm
    between them leaves such rows as they are)."""
    cfg = get_smoke("deepseek-v2-lite").replace(sliding_window=0)
    d = cfg.d_model
    plain = init_mla(KEY, cfg)
    low = init_mla(KEY, cfg.replace(q_lora_rank=d))
    assert "wq" in plain and "wq_a" not in plain and "wq" not in low
    q_a, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(5), (d, d)))
    low = dict(low, wkv_a=plain["wkv_a"], kv_norm=plain["kv_norm"],
               wkv_b=plain["wkv_b"], wo=plain["wo"], wq_a=q_a)
    plain = dict(plain, wq=q_a @ low["wq_b"])
    x = _x(T=16, d=d)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    pos = jnp.arange(16)
    with jax.default_matmul_precision("highest"):
        a, _ = mla_attention_block(plain, x, cfg, positions=pos, mode="train")
        b, _ = mla_attention_block(low, x, cfg.replace(q_lora_rank=d),
                                   positions=pos, mode="train")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("m,sizes", [(300, (40, 0, 97, 13)),
                                     (256, (64, 64, 64, 64))],
                         ids=["ragged-padded", "full"])
def test_grouped_matmul_pallas_matches_jnp(m, sizes):
    ks = jax.random.split(KEY, 3)
    lhs = jax.random.normal(ks[0], (m, 128), jnp.float32)
    rhs = jax.random.normal(ks[1], (4, 128, 256), jnp.float32) * 0.1
    dy = jax.random.normal(ks[2], (m, 256), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    def run(use_pallas):
        def f(a, b):
            y = kops.grouped_matmul(a, b, gs, use_pallas=use_pallas,
                                    interpret=True)
            return jnp.sum(y * dy), y
        (_, y), grads = jax.value_and_grad(f, argnums=(0, 1),
                                           has_aux=True)(lhs, rhs)
        return y, grads

    with jax.default_matmul_precision("highest"):
        y_p, g_p = run(True)
        y_j, g_j = run(False)
    held = int(sum(sizes))
    assert not np.any(np.asarray(y_p[held:]))          # rows past the groups
    for a, b in [(y_p, y_j), *zip(g_p, g_j)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_both_pod_rounds_report_the_expert_counters():
    """Every client priority, so both rounds run the same forwards: the
    same held rows."""
    from repro.configs.base import FedConfig
    from repro.fl import engine, sharded
    cfg = get_smoke("deepseek-v2-lite")
    model = get_model(cfg)
    C = 2
    fed = FedConfig(num_clients=C, num_priority=C, local_epochs=2, lr=0.01)
    toks = jax.random.randint(KEY, (C + 1, 17), 0, cfg.vocab_size)

    def rows(t):
        return {"tokens": t[..., :-1], "labels": t[..., 1:],
                "mask": jnp.ones(t[..., 1:].shape)}

    batch = {"clients": rows(toks[:C, None]), "server": rows(toks[C:]),
             "priority_mask": jnp.ones(C), "weights": jnp.ones(C)}
    state = engine.init_state(model.init(KEY), fed, C)
    got = [jax.jit(sharded.make_round_step(model, fed, C, fsdp=fsdp))(
        state, batch)[1] for fsdp in (False, True)]
    for stats in got:
        # 2 expert layers, 2 slots a token over 4 held of 8: at most all
        # 1 + C eval and C x 2 training forwards of 16 tokens
        assert 0 < int(stats["moe_held_rows"]) <= 2 * 2 * 16 * (1 + 3 * C)
        assert float(stats["moe_load_ratio"]) >= 1.0
    assert int(got[0]["moe_held_rows"]) == int(got[1]["moe_held_rows"])


EXPERT_MESH_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels import ops
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
key = jax.random.PRNGKey(0)
T, k, d, f, E, R = 64, 3, 32, 16, 8, 16
r = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape)
xf, topw = r(0, (T, d)), jax.nn.softmax(r(1, (T, k)))
tope = jax.random.randint(jax.random.fold_in(key, 2), (T, k), 0, R)
ws = (r(3, (E, d, f)), r(4, (E, d, f)), r(5, (E, f, d)))


def close(a, b):
    # f32 sums of a few hundred products, added in another order
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * np.abs(b).max())


def grads(**kw):
    def loss(xf, topw, *ws):
        y = ops.expert_ffn(xf, tope, topw, *ws, **kw)
        return jnp.sum(jnp.sin(y)), y
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)


with jax.default_matmul_precision("highest"):
    want = jax.jit(grads(use_pallas=False))(xf, topw, *ws)
    for ep in (False, True):
        # rows over data; the experts (ep) or their hidden dim over model
        fn = grads(use_pallas=True, interpret=True, expert_parallel=ep)
        with jax.set_mesh(mesh), ops.kernels_per_shard(mesh, ("data",),
                                                       ("model",)):
            step = jax.jit(fn).lower(xf, topw, *ws).compile()
            got = step(xf, topw, *ws)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            close(a, b)
        # each shard computes a quarter of the tokens with half the
        # weights: no shard gathers every row or every expert
        hlo = step.as_text()
        assert "all-gather" not in hlo, ep
    # inside a client vmap whose client axis maps over data
    xc, wc = jnp.stack([xf, -xf]), jnp.stack([topw, topw[::-1]])
    local = jax.vmap(grads(use_pallas=True, interpret=True,
                           expert_parallel=True),
                     in_axes=(0, 0, None, None, None),
                     spmd_axis_name=("data",))
    with jax.set_mesh(mesh), ops.kernels_per_shard(mesh, (), ("model",)):
        got = jax.jit(local)(xc, wc, *ws)
    plain = jax.vmap(grads(use_pallas=False), in_axes=(0, 0, None, None, None))
    for a, b in zip(jax.tree.leaves(got),
                    jax.tree.leaves(jax.jit(plain)(xc, wc, *ws))):
        close(a, b)
print("OK")
"""


def test_expert_ffn_runs_per_shard_on_a_mesh():
    """On a (data=2, model=2) mesh the expert layer's products run per
    shard: the token rows split over data, the experts (expert parallel)
    or their hidden dim over model, the partial sums added after. Forward
    and gradients match one device, also inside a client vmap. Four host
    devices need their own process; the kernel runs in interpret mode."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", EXPERT_MESH_CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]
