"""The whole round's share of the chips' peak: the model FLOPs the traced
rounds require (``bench/flops.py``: 6·N per trained token and 2·N per
forward-only token, plus causal attention; no recomputation) over the
traced window times the chips times the bf16 peak."""


def read(ctx):
    peaks, tr, flops = ctx["peaks"], ctx["trace"], ctx["flops"]
    if peaks is None or tr["window_s"] <= 0 or not tr["busy_s"]:
        return None
    need = sum(flops.round_work(ctx["family"], ctx["mc"], ctx["traffic"],
                                int(sum(g > 0 for g in r["gates"])))
               ["model_flops"] for r in ctx["rounds"])
    return 100.0 * need / (tr["window_s"] * ctx["chips"]
                           * peaks["bf16_flops_per_s"])
