"""Device ms per traced round in the server step: the divergence guard,
the server optimizer, the next state and stats (``fedalign.server_step``,
read by ``bench/phases.py``). None where the program names no phase."""
import phases


def read(ctx):
    return phases.round_ms(ctx, ("server_step",))
