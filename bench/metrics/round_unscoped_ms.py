"""Device ms per traced round that no phase names: the busy time less the
named phases' (``bench/phases.py``), such as copies and converts the
compiler put in outside any phase. None where the program names no
phase."""
import phases


def read(ctx):
    return phases.round_ms(ctx, (phases.UNSCOPED,))
