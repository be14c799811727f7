"""The comparison that decides ``correct``.

A run's first three rounds go through the window's own compiled step and
feed; the reference (``bench/reference.py``) follows the same three rounds
from the same weights and rows. Four numbers are compared, each against the
limit that ``bench/limits/<cell>.json`` gives it:

- ``loss_gap``: the largest |program - reference| over the three rounds'
  server losses and eval pre-pass losses, in nats.
- ``gates_diff``: how many gates of the three rounds differ (limit 0).
- ``delta1_gap``: the first aggregated delta, as the server step got it
  (the change of the weights after round one, since the server step is
  plain SGD at rate 1). By the worst leaf: the gap between the program's
  norm and the reference's, over the larger of the reference's norm of
  that leaf and of the median leaf.
- ``change3_gap``: the same, for the change of the weights after three
  rounds.

A leaf is the weights of one layer of one projection (the stacked layer
axis is split). Leaves whose first reference delta is under a thousandth
of the median leaf's are left out of both gaps: their gradient is nought
to rounding (a key bias under softmax), so they move by round-off alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import flatten, init_flat

NUMBERS = ("loss_gap", "gates_diff", "delta1_gap", "change3_gap")
QUIET = 1e-3


def change_norms_fn(family, mc):
    """jit: (key, params) -> {leaf: norm of params - weights(key)}, with the
    stacked layer axis (the program's ``periods``) split into one leaf per
    layer."""
    def fn(key, params):
        init = init_flat(family, mc, key)
        out = {}
        for path, x in flatten(params).items():
            d = (x.astype(jnp.float32) - init[path]).astype(jnp.float32)
            if path.startswith("periods."):
                out[path] = jnp.sqrt(jnp.sum(d * d, axis=tuple(
                    range(1, d.ndim))))
            else:
                out[path] = jnp.sqrt(jnp.sum(d * d))
        return out
    return jax.jit(fn)


def leaf_norms(tree):
    """{leaf: norm} from ``change_norms_fn``'s output, per layer."""
    out = {}
    for path, v in tree.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{path}/{i}": float(x) for i, x in enumerate(v)})
        else:
            out[path] = float(v)
    return out


def _norm_gap(prog, ref, keep):
    floor = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keep)


def readings(prog, ref, tie_tol=0.0):
    """The four numbers of a run, from its observations and the
    reference's (both as ``Session.start`` / ``Session.reference`` return
    them), plus the loss gap of each round for the record. A gate counts as
    different only where the reference's loss gap lies more than
    ``tie_tol`` from eps."""
    med = float(np.median(list(ref["delta1"].values())))
    keep = [k for k, v in ref["delta1"].items() if v >= QUIET * med]
    per_round = np.maximum(
        np.abs(np.asarray(prog["server_loss"])
               - np.asarray(ref["server_loss"])),
        np.max(np.abs(np.asarray(prog["local_losses"])
                      - np.asarray(ref["local_losses"])), axis=1))
    clear = np.asarray(ref["margin"]) >= tie_tol
    flips = (np.asarray(prog["gates"]) > 0) != (np.asarray(ref["gates"]) > 0)
    return {
        "loss_gap": float(np.max(per_round)),
        "gates_diff": int(np.sum(flips & clear)),
        "delta1_gap": float(_norm_gap(prog["delta1"], ref["delta1"], keep)),
        "change3_gap": float(_norm_gap(prog["change3"], ref["change3"], keep)),
        "quiet_leaves": len(ref["delta1"]) - len(keep),
        "loss_gap_rounds": per_round.tolist(),
        "gate_margin_min": float(np.min(ref["margin"])),
    }


def judge(numbers, limits):
    """(correct, checks): each compared number beside its limit. A number
    that is not finite fails."""
    checks = {}
    ok = True
    for name in NUMBERS:
        v, lim = numbers[name], limits[name]
        good = bool(np.isfinite(v) and v <= lim)
        ok &= good
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
