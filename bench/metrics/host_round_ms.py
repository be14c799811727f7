"""Host milliseconds per round outside the step call: the batch build, the
``device_put`` and the read of the stats, from the harness's own spans in
the traced window."""

HOST_SPANS = ("bench.build", "bench.put", "bench.read")


def read(ctx):
    tr = ctx["trace"]
    rounds = tr["span_counts"].get("bench.step", 0)
    if not rounds:
        return None
    host = sum(tr["span_seconds"].get(n, 0.0) for n in HOST_SPANS)
    return 1e3 * host / rounds
