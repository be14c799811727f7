"""Device ms per traced round in the aggregation of the trained clients
(``fedalign.aggregate``, read by ``bench/phases.py``). None where the
program names no phase."""
import phases


def read(ctx):
    return phases.round_ms(ctx, ("aggregate",))
