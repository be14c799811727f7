"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window), mean over the
chips."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0 or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
