"""Readings for the limits of the correctness check, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--controls bf16,fp8] [--faults half_batch,answer_altered]

For each seed, in one process with one compiled round: the program's first
rounds (through the window's own step and feed, as a run's set-up makes
them), the float32 reference, and each control: the reference computed one
step below a precision that the configuration states (``bf16``: weights
and updates in bfloat16; ``fp8``: matmul operands in float8 e4m3). Prints
one JSON line per seed with the numbers of ``bench/check.py`` for the
program and for each control, each against the reference. The benchmark's
own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="bf16,fp8")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    import harness
    import check
    from faults import FAULTS
    res = harness.resolve(ROOT, args.workload)
    sess = harness.Session(res, harness.chip_devices(res))
    controls = [c for c in args.controls.split(",") if c]
    faults = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        prog = sess.start(seed)
        sess.free()
        planted = {}
        for f in faults:
            sess.step = FAULTS[f](sess, sess.compiled)
            planted[f] = sess.start(seed)
            sess.step = sess.compiled
            sess.free()
        tol = res["limits"]["loss_gap"]
        ref = sess.reference("f32", follow=prog, tol=tol)
        line = {"workload": args.workload, "seed": seed,
                "program": check.readings(prog, ref, tol)}
        for c in controls:
            line[c] = check.readings(sess.reference(c), ref, tol)
        for f, obs in planted.items():
            line[f] = check.readings(obs, ref, tol)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
