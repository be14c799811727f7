"""Flash attention's share of its roofline: the least time one chip could
take on the traced rounds' attention work (the larger of the required
FLOPs over the bf16 peak and the required bytes over HBM bandwidth, from
the cell's shapes in ``bench/flops.py``) over the device seconds of the
flash forward and backward kernels. The work counts every client the round
trains (the spatial round trains gated-out clients too), the eval pre-pass
and the server loss; recomputation under remat is not work, so it shows as
a lower share."""
import re
import sys

# the program names its flash kernels kernel.flash_fwd, kernel.flash_bwd_dq
# and kernel.flash_bwd_dkv; the trace names an operation by its instruction
# ("%kernel.flash_fwd.37 = ..."), so another Pallas kernel, fedagg or a
# family's own, is not counted
FLASH = re.compile(r"^%?kernel\.flash_[\w.\-]* = ")


def is_flash(name):
    return bool(FLASH.match(name))


def read(ctx):
    peaks, tr, flops = ctx["peaks"], ctx["trace"], ctx["flops"]
    if peaks is None:
        return None
    kernel_s = ctx["kernel_seconds"](tr, is_flash)
    if not kernel_s:
        return None
    C = ctx["traffic"]["clients"]
    fl = by = 0.0
    for r in ctx["rounds"]:
        trained = int(sum(g > 0 for g in r["gates"]))
        w = flops.round_work(ctx["family"], ctx["mc"], ctx["traffic"],
                             trained, train_calls=C if ctx["mode"] == "spatial"
                             else trained)
        fl += w["attn_flops"]
        by += w["attn_bytes"]
    t_flops = fl / ctx["chips"] / peaks["bf16_flops_per_s"]
    t_bytes = by / ctx["chips"] / peaks["hbm_bytes_per_s"]
    print(f"[bench] flash_attn_roofline: "
          f"{'compute' if t_flops >= t_bytes else 'memory'} bound "
          f"({t_flops:.6g}s of FLOPs, {t_bytes:.6g}s of bytes, "
          f"{kernel_s:.6g}s in the kernels)", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / kernel_s
