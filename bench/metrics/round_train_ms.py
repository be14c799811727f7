"""Device ms per traced round in the clients' local training
(``fedalign.train``, read by ``bench/phases.py``). None where the program
names no phase."""
import phases


def read(ctx):
    return phases.round_ms(ctx, ("train",))
