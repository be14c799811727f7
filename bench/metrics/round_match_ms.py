"""Device ms per traced round in FedALIGN's matching statistic and gate:
the server loss, the clients' eval of the received model and the gate
(``fedalign.server_loss`` + ``fedalign.eval`` + ``fedalign.gate``, read
by ``bench/phases.py``). None where the program names no phase."""
import phases


def read(ctx):
    return phases.round_ms(ctx, ("server_loss", "eval", "gate"))
