"""Device ms per traced round in the expert layer: the operations under
the program's ``kernel.moe_route``, ``kernel.moe_gmm`` and
``kernel.moe_combine`` scopes (``models/moe.py``), joined to the trace by
``bench/phases.py``. None where the program names no phase or has no such
scope."""
import phases

SCOPES = ("moe_route", "moe_gmm", "moe_combine")


def read(ctx):
    if not ctx["trace"]["busy_s"] or not ctx["rounds"]:
        return None
    got = phases.round_phases(ctx)
    if got is None or not any(k in got["kernels"] for k in SCOPES):
        return None
    return 1e3 * sum(got["kernels"].get(k, 0.0) for k in SCOPES) \
        / len(ctx["rounds"])
