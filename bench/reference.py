"""The plain reference: weights from the seed, and FedALIGN rounds in
straightforward ``jax.numpy``.

Nothing here imports the program. The model is the configuration's
family, ``bench/families/<family>.py``, which gives the program's
parameter layout (``param_shapes``), the plain model loss (``loss``) and
the model's counts; this module holds what every family shares: weights
drawn leaf by leaf from the seed, the arithmetic of each precision, and
the round. It reads the model's sizes from the configuration file
(``bench/configs/<config>.json``) and the federation's knobs from the
traffic mix, and follows the program's layout only so that both can
start from the same weights.

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
def _leaf(key, path, shape, kind):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7fffffff)
    if kind == "norm":
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    if kind in ("embed", "bias"):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    fan_in = shape[-2]
    return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * fan_in ** -0.5)


def nest(flat, lists=()):
    """{"a.b.c": x} -> {"a": {"b": {"c": x}}}. The top-level containers
    named in ``lists`` are lists, ordered by index ("a.0.c", "a.1.c"),
    and are there even where no path leads into them."""
    out = {name: {} for name in lists}
    for path, x in flat.items():
        node = out
        *heads, last = path.split(".")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = x
    for name in lists:
        out[name] = [out[name][i] for i in sorted(out[name], key=int)]
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


def init_flat(family, mc, key):
    """The weights as {path: f32 array} in the family's layout; traced
    inside one jit."""
    return {path: _leaf(key, path, shape, kind)
            for path, (shape, kind) in family.param_shapes(mc).items()}


def init_params(family, mc, key):
    """The weights nested as the program nests them."""
    return nest(init_flat(family, mc, key), family.LISTS)


def make_init(family, mc, shardings=None):
    """One jitted call from the key to the nested weights on the device."""
    fn = lambda key: init_params(family, mc, key)                # noqa: E731
    return jax.jit(fn, out_shardings=shardings)


# --------------------------------------------------------------- arithmetic
class Arith:
    """Matrix products in one precision, and the type that weights,
    activations and updates take in it."""

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


# ------------------------------------------------------------ shared layers
def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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
    """Jitted pieces of one plain round, for one configuration of a family,
    one traffic mix and one precision."""

    def __init__(self, family, mc, traffic, precision="f32"):
        self.mc, self.traffic, self.precision = mc, traffic, precision
        ar = Arith(precision)
        self.dtype = ar.dtype
        lossf = functools.partial(family.loss, mc, precision)
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
