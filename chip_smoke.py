"""Chip smoke test: the FedALIGN main path on a TPU at Qwen1.5-0.5B's
published widths, with random weights made from ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips (a 2x2 v5e host)

One chip: five full-width rounds through ``launch.train.run`` (the round
is chosen by compiling it against device memory; on one v5e it is the
temporal round), then
each main-path Pallas kernel compiled for the chip and checked against
its oracle in ``kernels/ref.py`` at real widths. ``--chips 4`` runs only
the sharded pod round: two rounds on a (data=4, model=1) mesh, where the
trainer chooses the spatial round with one client per chip, compared
with two on chip 0 of the same process, where it chooses the temporal
round, with the same seed and batches.

Everything runs in this one process: a chip belongs to one process. The
script exits nonzero and prints no result when JAX finds no TPU, or when
any check or phase fails. The last line of standard output is the JSON
result, ``{"ok": true, "device": {...}}``.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.fedagg import fedagg_pallas  # noqa: E402
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.utils import enable_compile_cache, param_count  # noqa: E402

ARCH = "qwen1.5-0.5b"
ROUND = dict(arch=ARCH, smoke=False, clients=4, n_priority=2, per_client=4,
             seq=512, local_epochs=2)
FEDAGG_CLIENTS = 4
ATTN_SHAPE = (4, 512, 16, 64)           # B, S, H (= KV), hd
# (atol, rtol): |got - want| <= atol + rtol * |want| elementwise
F32_TOL = (2e-5, 2e-5)                  # f32 sums in another order
BF16_TOL = (2e-2, 2e-2)                 # bf16 inputs / outputs
# --chips 4: spatial (4 chips) vs temporal (chip 0) after the same rounds.
# Both train in bf16 compute with f32 params; only the layout and the
# summation order differ, so losses agree to one bf16 ulp and params to a
# small fraction of one round's update.
LOSS_RTOL = 2.0 ** -8
PARAM_TOL = (1e-4, 1e-3)


def log(msg):
    print(msg, flush=True)


def compiled(fn, *args):
    """jit + AOT-compile ``fn``; returns (compiled, seconds, is_mosaic)."""
    t0 = time.perf_counter()
    c = jax.jit(fn).lower(*args).compile()
    return c, time.perf_counter() - t0, "tpu_custom_call" in c.as_text()


def scaled_err(got, want, tol):
    """(max abs error, max of |got - want| / (atol + rtol |want|))."""
    atol, rtol = tol
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    d = jnp.abs(got - want)
    return jnp.max(d), jnp.max(d / (atol + rtol * jnp.abs(want)))


def chunked_err(got, want_fn, operands, tol, chunk=1 << 24):
    """``scaled_err`` of an [M] kernel output against ``want_fn`` over
    column chunks of the [C, M] / [M] ``operands`` (the oracles hold whole
    [C, M] temporaries, which at LM width would not fit beside the inputs).
    The last chunk overlaps the one before it."""
    M = got.shape[0]
    chunk = min(chunk, M)

    @jax.jit
    def one(start, got, *ops):
        cut = [jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=x.ndim - 1)
               if x.shape[-1] == M else x for x in ops]
        return scaled_err(jax.lax.dynamic_slice_in_dim(got, start, chunk),
                          want_fn(*cut), tol)

    errs = [one(jnp.int32(min(s, M - chunk)), got, *operands)
            for s in range(0, M, chunk)]
    return (max(float(e[0]) for e in errs), max(float(e[1]) for e in errs))


def report(name, err, tol, impl, sec):
    ok = float(err[1]) <= 1.0
    log(f"[kernel] {name}: impl={impl} compile={sec:.2f}s "
        f"max_abs_err={float(err[0]):.3e} tol=(atol={tol[0]:g}, rtol={tol[1]:g}) "
        f"{'ok' if ok else 'FAIL'}")
    return ok


def kernel_phase(seed, M, interpret=False):
    """Each main-path kernel, compiled, against its ``kernels/ref.py``
    oracle: fedagg (mean, int8 wire, dp, trimmed_mean, median) at
    C = 4 x M columns, flash attention forward and backward."""
    impl_of = {True: "pallas-mosaic (compiled)",
               False: "pallas (interpret)" if interpret else "NOT a kernel"}
    ok = True
    C = FEDAGG_CLIENTS
    key = jax.random.PRNGKey(seed)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (C,)) + 0.05
    g = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    agg = functools.partial(fedagg_pallas, interpret=interpret)

    def run(name, fn, want_fn, operands):
        c, sec, mosaic = compiled(fn, *operands)
        err = chunked_err(c(*operands), want_fn, operands, F32_TOL)
        return (report(name, err, F32_TOL, impl_of[mosaic], sec)
                and (mosaic or interpret))

    # int8 wire first, alone: its [C, M] payload is a quarter of the f32 one
    q = jax.jit(lambda k: jax.random.randint(k, (C, M), -127, 128, jnp.int8))(
        jax.random.fold_in(key, 2))
    s = jax.random.uniform(jax.random.fold_in(key, 3), (C,)) * 1e-2
    ok &= run("fedagg int8+mean",
              lambda u, w, g, s: agg(u, w, g, codec="int8", dequant_scale=s),
              lambda u, w, g, s: ref.fedagg_ref(ref.decode_int8_ref(u, s), w, g),
              (q, w, g, s))
    del q
    u = jax.jit(lambda k: jax.random.normal(k, (C, M), jnp.float32))(
        jax.random.fold_in(key, 4))
    ok &= run("fedagg mean", agg, ref.fedagg_ref, (u, w, g))
    ok &= run("fedagg trimmed_mean",
              lambda u, w, g: agg(u, w, g, aggregator="trimmed_mean",
                                  trim_frac=0.25),
              lambda u, w, g: ref.fedagg_trimmed_ref(u, w, g, 0.25), (u, w, g))
    ok &= run("fedagg median",
              lambda u, w, g: agg(u, w, g, aggregator="median"),
              ref.fedagg_median_ref, (u, w, g))
    rs = jnp.asarray([1.0, 0.5, 0.25, 0.8])
    noise = jax.jit(lambda k: jax.random.normal(k, (M,), jnp.float32))(
        jax.random.fold_in(key, 5))
    ok &= run("fedagg dp",
              lambda u, w, g, rs, nz: agg(u, w, g, aggregator="dp", row_scale=rs,
                                          noise=nz, noise_scale=0.7),
              lambda u, w, g, rs, nz: ref.fedagg_dp_ref(u, w, g, rs, nz, 0.7),
              (u, w, g, rs, noise))
    del u, noise

    qkv = [jax.random.normal(jax.random.fold_in(key, 10 + i), ATTN_SHAPE,
                             jnp.bfloat16) for i in range(3)]
    ct = jax.random.normal(jax.random.fold_in(key, 13), ATTN_SHAPE)
    flash = functools.partial(flash_attention_pallas, interpret=interpret)
    with jax.default_matmul_precision("float32"):
        want = ref.attention_ref(*qkv)
        want_grads = jax.grad(lambda q, k, v: jnp.sum(
            ref.attention_ref(q, k, v).astype(jnp.float32) * ct),
            argnums=(0, 1, 2))(*qkv)
    c, sec, mosaic = compiled(lambda q, k, v: flash(q, k, v), *qkv)
    ok &= report("flash_attention fwd", scaled_err(c(*qkv), want, BF16_TOL),
                 BF16_TOL, impl_of[mosaic], sec) and (mosaic or interpret)
    c, sec, mosaic = compiled(jax.grad(lambda q, k, v: jnp.sum(
        flash(q, k, v).astype(jnp.float32) * ct), argnums=(0, 1, 2)), *qkv)
    for name, got, want in zip(("dq", "dk", "dv"), c(*qkv), want_grads):
        ok &= report(f"flash_attention bwd {name}",
                     scaled_err(got, want, BF16_TOL), BF16_TOL,
                     impl_of[mosaic], sec) and (mosaic or interpret)
    return ok


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def memory_line(device):
    """The allocator's counters as the device reports them."""
    stats = device.memory_stats() or {}
    return " ".join(f"{k}={stats[k]}" for k in sorted(stats)
                    if "bytes" in k)


def chosen(hist, want, tag):
    """Whether the trainer chose the ``want`` round (it decides from the
    device's memory; see ``fl/sharded.choose_round``)."""
    ok = hist[0]["round_mode"] == want
    log(f"[{tag}] round={hist[0]['round_mode']} (expected {want}) "
        f"{'ok' if ok else 'FAIL'}")
    return ok


def train_phase(seed, rounds=5, **round_kw):
    """``rounds`` FedALIGN rounds through the trainer on chip 0: the
    temporal round chosen, every server loss finite and the last below
    the first."""
    dev = jax.devices()[0]
    _, hist = train.run(**round_kw, rounds=rounds, seed=seed,
                        mesh=make_host_mesh(devices=[dev]))
    losses = [h["server_loss"] for h in hist]
    secs = [h["sec"] for h in hist]
    log(f"[train] compile_sec={hist[0]['compile_sec']:.2f} "
        f"round_sec={[round(s, 4) for s in secs]} "
        f"steady_round_sec={np.median(secs[1:] or secs):.4f}")
    log(f"[train] server_loss first={losses[0]:.6f} last={losses[-1]:.6f}")
    log(f"[train] peak_bytes_in_use={peak_bytes(dev)}")
    log(f"[train] memory_stats {memory_line(dev)}")
    ok = chosen(hist, "temporal", "train")
    if not all(np.isfinite(losses)):
        log("[train] FAIL: a server loss is not finite")
        ok = False
    if not losses[-1] < losses[0]:
        log("[train] FAIL: the final server loss is not below the first")
        ok = False
    return ok


def four_chip_phase(seed, rounds=2, **round_kw):
    """The trainer on a (data=4, model=1) mesh, where it chooses the
    spatial round (one client per chip), against the trainer on chip 0,
    where it chooses the temporal round, with the same seed and batches."""
    devs = jax.devices()[:4]
    p_s, h_s = train.run(**round_kw, rounds=rounds, seed=seed,
                         mesh=make_host_mesh(devices=devs))
    p_s = jax.device_get(p_s)
    log(f"[4chip] spatial peak_bytes_in_use="
        f"{[peak_bytes(d) for d in devs]}")
    p_t, h_t = train.run(**round_kw, rounds=rounds, seed=seed,
                         mesh=make_host_mesh(devices=devs[:1]))
    p_t = jax.device_get(p_t)
    ok = chosen(h_s, "spatial", "4chip") & chosen(h_t, "temporal", "4chip")
    for a, b in zip(h_s, h_t):
        gates_eq = np.array_equal(a["gates"], b["gates"])
        rel = abs(a["server_loss"] - b["server_loss"]) / abs(b["server_loss"])
        log(f"[4chip] round {a['round']}: spatial loss={a['server_loss']:.6f} "
            f"({a['sec']:.4f}s) temporal loss={b['server_loss']:.6f} "
            f"({b['sec']:.4f}s) rel_diff={rel:.3e} (tol {LOSS_RTOL:.3e}) "
            f"gates={a['gates'].tolist()} equal={gates_eq}")
        ok &= gates_eq and rel <= LOSS_RTOL and bool(np.isfinite(rel))
    atol, rtol = PARAM_TOL
    worst, worst_abs = 0.0, 0.0
    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_t)):
        d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
        worst_abs = max(worst_abs, float(d.max()))
        worst = max(worst, float((d / (atol + rtol * np.abs(b))).max()))
    log(f"[4chip] params max_abs_diff={worst_abs:.3e} "
        f"tol=(atol={atol:g}, rtol={rtol:g}) scaled={worst:.3f}")
    log(f"[4chip] compile_sec spatial={h_s[0]['compile_sec']:.2f} "
        f"temporal={h_t[0]['compile_sec']:.2f}")
    return ok and worst <= 1.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache}")
    if args.chips == 4:
        ok = four_chip_phase(args.seed, **ROUND)
    else:
        M = param_count(jax.eval_shape(get_model(get_config(ARCH)).init,
                                       jax.random.PRNGKey(0)))
        ok = train_phase(args.seed, **ROUND)
        ok &= kernel_phase(args.seed, M)
    if not ok:
        print("chip_smoke: a check failed (see the lines above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
