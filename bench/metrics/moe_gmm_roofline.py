"""The grouped expert products' share of their roofline: the least time
one chip could take on the held experts' products of the traced rounds
(the larger of the FLOPs over the bf16 peak and the bytes over HBM
bandwidth, from the family's ``expert_work`` at the balanced load: forward
and backward for each trained client's local steps, forward for the eval
pre-pass and the server loss) over the device seconds under the program's
``kernel.moe_gmm`` scope (``bench/phases.py``). Recomputation under remat
is not work, so it shows as a lower share. None where the family counts
no expert work or the program has no such scope."""
import sys

import phases


def read(ctx):
    peaks, fam = ctx["peaks"], ctx["family"]
    if peaks is None or not hasattr(fam, "expert_work") \
            or not ctx["trace"]["busy_s"] or not ctx["rounds"]:
        return None
    got = phases.round_phases(ctx)
    kernel_s = got and got["kernels"].get("moe_gmm")
    if not kernel_s:
        return None
    tr, mc = ctx["traffic"], ctx["mc"]
    tokens = tr["per_client"] * tr["seq"]
    fl = by = 0.0
    for r in ctx["rounds"]:
        trained = int(sum(g > 0 for g in r["gates"]))
        for n, backward in (((tr["clients"] + 1), False),
                            (trained * tr["local_steps"], True)):
            f, b = fam.expert_work(mc, n * tokens, backward)
            fl, by = fl + f, by + b
    t_flops = fl / ctx["chips"] / peaks["bf16_flops_per_s"]
    t_bytes = by / ctx["chips"] / peaks["hbm_bytes_per_s"]
    print(f"[bench] moe_gmm_roofline: "
          f"{'compute' if t_flops >= t_bytes else 'memory'} bound "
          f"({t_flops:.6g}s of FLOPs, {t_bytes:.6g}s of bytes, "
          f"{kernel_s:.6g}s in the kernels)", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / kernel_s
