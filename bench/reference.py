"""The plain reference: weights from the seed, and FedALIGN rounds of a
dense decoder in straightforward ``jax.numpy``.

Nothing here imports the program. The reference reads the model's sizes
from the configuration file (``bench/configs/<config>.json``) and the
federation's knobs from the traffic mix, and follows the program's
parameter layout only so that both can start from the same weights:

    embed [V, d], final_norm.scale [d], lm_head [d, V] (untied),
    periods.l0.{norm1,norm2}.scale [L, d],
    periods.l0.attn.{wq,wk,wv} [L, d, heads*hd], wo [L, heads*hd, d],
    periods.l0.attn.{bq,bk,bv} (with attention_bias),
    periods.l0.mlp.{w_gate,w_up} [L, d, ff], w_down [L, ff, d],
    pre_blocks [] (no leading dense layers).

One round, as the paper states it: the server's loss F(w) on the server
batch; each client's loss F_k(w) on its own batch; gates 1 for priority
clients and for non-priority clients with |F_k - F| < eps; E full-batch
SGD steps for each included client; the gated weighted mean of the
client deltas; the server step w + server_lr * mean (plain SGD, lr 1).

``precision`` picks the arithmetic: ``"f32"`` (float32, matmuls at
``highest``) is the reference; ``"bf16"`` (weights, activations and
updates in bfloat16) and ``"fp8"`` (float32 weights, matmul operands
rounded to float8 e4m3 under a scale per operand) are the controls, one
step below the two precisions that the configuration states (float32
weights, bfloat16 compute).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("f32", "bf16", "fp8")


# ------------------------------------------------------------------ weights
def param_shapes(mc):
    """{path: (shape, kind)} of the program's parameter layout."""
    d, V, L = mc["hidden_size"], mc["vocab_size"], mc["num_hidden_layers"]
    H, KV, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    ff = mc["intermediate_size"]
    out = {"embed": ((V, d), "embed"), "final_norm.scale": ((d,), "norm")}
    if not mc["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "matrix")
    p = "periods.l0."
    out.update({
        p + "norm1.scale": ((L, d), "norm"),
        p + "norm2.scale": ((L, d), "norm"),
        p + "attn.wq": ((L, d, H * hd), "matrix"),
        p + "attn.wk": ((L, d, KV * hd), "matrix"),
        p + "attn.wv": ((L, d, KV * hd), "matrix"),
        p + "attn.wo": ((L, H * hd, d), "matrix"),
        p + "mlp.w_gate": ((L, d, ff), "matrix"),
        p + "mlp.w_up": ((L, d, ff), "matrix"),
        p + "mlp.w_down": ((L, ff, d), "matrix"),
    })
    if mc["attention_bias"]:
        out.update({p + "attn.bq": ((L, H * hd), "bias"),
                    p + "attn.bk": ((L, KV * hd), "bias"),
                    p + "attn.bv": ((L, KV * hd), "bias")})
    return out


def _leaf(key, path, shape, kind):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7fffffff)
    if kind == "norm":
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    if kind in ("embed", "bias"):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    fan_in = shape[-2]
    return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * fan_in ** -0.5)


def nest(flat):
    """{"a.b.c": x} -> {"a": {"b": {"c": x}}}, plus the empty pre_blocks."""
    out = {"pre_blocks": []}
    for path, x in flat.items():
        node = out
        *heads, last = path.split(".")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = x
    return out


def flatten(tree):
    """Inverse of ``nest``: {"a.b.c": x}."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = x
    return out


def seed_key(seed: int):
    """A PRNG key from any whole seed (more than 32 bits are folded in)."""
    key = jax.random.PRNGKey(seed & 0xffffffff)
    return jax.random.fold_in(key, (seed >> 32) & 0x7fffffff)


def init_flat(mc, key):
    """The weights as {path: f32 array}; traced inside one jit."""
    return {path: _leaf(key, path, shape, kind)
            for path, (shape, kind) in param_shapes(mc).items()}


def make_init(mc, shardings=None):
    """One jitted call from the key to the nested weights on the device."""
    fn = lambda key: nest(init_flat(mc, key))                    # noqa: E731
    return jax.jit(fn, out_shardings=shardings)


# ---------------------------------------------------------------- the model
class _Arith:
    def __init__(self, precision):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.precision = precision
        self.dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

    def mm(self, a, b):
        if self.precision == "fp8":
            a, b = _fp8(a), _fp8(b)
        return a @ b

    def einsum(self, spec, a, b):
        if self.precision == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b)


def _fp8(x):
    """``x`` with its values rounded to float8 e4m3 under one scale that
    maps its largest magnitude to the format's largest (448); the gradient
    passes through unrounded, as scaled float8 training keeps its
    gradients in a wider type."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(mc, ar, x, p):
    B, S, d = x.shape
    H, KV, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    eps = mc["rms_norm_eps"]
    h = _rmsnorm(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q, k, v = ar.mm(h, a["wq"]), ar.mm(h, a["wk"]), ar.mm(h, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, H, hd), mc["rope_theta"])
    k = _rope(k.reshape(B, S, KV, hd), mc["rope_theta"])
    v = v.reshape(B, S, KV, hd)
    if KV != H:
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = ar.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(hd ** -0.5, x.dtype)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    allowed = ki <= qi
    window = (mc.get("sliding_window")
              if mc.get("use_sliding_window", True) else None)
    if window and window < S:
        allowed &= qi - ki < window
    s = jnp.where(allowed, s, jnp.asarray(-1e30, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = ar.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    x = x + ar.mm(o, a["wo"])
    h = _rmsnorm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    g = ar.mm(h, m["w_gate"])
    return x + ar.mm(jax.nn.silu(g) * ar.mm(h, m["w_up"]), m["w_down"])


def loss(mc, precision, params, batch):
    """Mean next-token cross-entropy over the batch's masked positions."""
    ar = _Arith(precision)
    x = params["embed"][batch["tokens"]]
    body = jax.checkpoint(lambda x, p: (_block(mc, ar, x, p), None))
    x, _ = jax.lax.scan(body, x, params["periods"]["l0"])
    x = _rmsnorm(x, params["final_norm"]["scale"], mc["rms_norm_eps"])
    w = (params["embed"].T if mc["tie_word_embeddings"]
         else params["lm_head"])
    logits = ar.mm(x, w).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------- one round
def split_rows(x):
    """[..., seq+1] token rows -> tokens, labels, mask."""
    return {"tokens": jnp.asarray(x[..., :-1]),
            "labels": jnp.asarray(x[..., 1:]),
            "mask": jnp.ones(x[..., 1:].shape, jnp.float32)}


def round_batches(fed_data, draws):
    """The round's client and server batches from the raw streams and the
    two row draws the round was fed with."""
    client_idx, server_idx = draws
    toks = fed_data["tokens"]
    rows = np.stack([toks[c, client_idx[c]] for c in range(toks.shape[0])])
    return rows, fed_data["test_tokens"][server_idx]


class Reference:
    """Jitted pieces of one plain round, for one configuration, one traffic
    mix and one precision."""

    def __init__(self, mc, traffic, precision="f32"):
        self.mc, self.traffic, self.precision = mc, traffic, precision
        ar = _Arith(precision)
        self.dtype = ar.dtype
        lossf = functools.partial(loss, mc, precision)
        lr = jnp.asarray(traffic["lr"], ar.dtype)
        E = traffic["local_steps"]

        def train(params, batch):
            def step(p, _):
                g = jax.grad(lossf)(p, batch)
                return jax.tree.map(lambda a, b: a - lr * b, p, g), None
            p, _ = jax.lax.scan(step, params, None, length=E)
            return p

        def add(acc, params, trained, wg):
            wg = wg.astype(ar.dtype)
            return jax.tree.map(lambda a, p, t: a + wg * (t - p),
                                acc, params, trained)

        def server(params, acc, den):
            return jax.tree.map(
                lambda p, a: p + jnp.where(den > 0, a / jnp.maximum(den, 1e-30),
                                           0.0).astype(p.dtype), params, acc)

        self._loss = jax.jit(lossf)
        self._train = jax.jit(train)
        self._add = jax.jit(add, donate_argnums=0)
        self._server = jax.jit(server, donate_argnums=0)

    def _precise(self):
        return jax.default_matmul_precision(
            "highest" if self.precision != "bf16" else "default")

    def cast(self, params):
        return jax.tree.map(lambda a: a.astype(self.dtype), params)

    def round(self, params, fed_data, draws, tie=None):
        """One round from ``params``; returns (params', observations).

        ``tie`` = (gates, tol): where a client's |F_k - F| lies within
        ``tol`` of eps, either gate is right to rounding, and the round
        takes the given gate for it, so that the two trajectories stay
        comparable. Observations record each client's margin to eps."""
        tr = self.traffic
        client_rows, server_rows = round_batches(fed_data, draws)
        C = client_rows.shape[0]
        pm = np.asarray(fed_data["priority_mask"], bool)
        w = np.asarray(fed_data["weights"], np.float32)
        with self._precise():
            server_loss = float(self._loss(params, split_rows(server_rows)))
            batches = [split_rows(client_rows[c]) for c in range(C)]
            local = np.array([float(self._loss(params, b)) for b in batches])
            margin = np.where(pm, np.inf,
                              np.abs(np.abs(local - server_loss)
                                     - tr["epsilon"]))
            gates = np.where(pm | (np.abs(local - server_loss)
                                   < tr["epsilon"]), 1.0, 0.0)
            if tie is not None:
                given, tol = tie
                gates = np.where(margin < tol, np.asarray(given) > 0,
                                 gates > 0).astype(np.float64)
            acc = jax.tree.map(lambda a: jnp.zeros(a.shape, self.dtype),
                               params)
            den = 0.0
            for c in range(C):
                if gates[c] > 0:
                    wg = float(w[c] * gates[c])
                    acc = self._add(acc, params, self._train(params, batches[c]),
                                    jnp.float32(wg))
                    den += wg
            params = self._server(params, acc, jnp.float32(den))
        return params, {"server_loss": server_loss, "local_losses": local,
                        "gates": gates, "margin": margin}
