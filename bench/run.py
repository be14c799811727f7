"""Benchmark entry point: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, the model family that the configuration names
(``bench/families/<family>.py``), traffic mix, per-layer metric readers
and limits are files under ``bench/`` found by name
(``bench/harness.py``). The run exits nonzero and prints no result where
JAX finds no TPU, fewer chips than the cell asks for, or a device kind
that the peaks table (``bench/peaks.json``) does not hold. Otherwise it
sets up (weights from the seed, the round compiled, the first rounds run
and kept for the correctness check), runs rounds for ``--seconds``, checks
the first rounds against the plain reference, and prints the numbers
compared beside their limits as its last lines on standard error, and the
result as the last line of standard output. ``--trace 1`` traces a window
of the cell's ``trace_rounds`` rounds and reports the per-layer metrics
instead of the end-to-end ones.
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


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit("bench/run.py: the program (src/repro) is not in this "
                 "checkout")
    import harness
    res = harness.resolve(ROOT, args.workload)
    result = harness.run(res, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, devices=harness.chip_devices(res),
                         trace_dir=os.path.join(ROOT, ".bench_trace"))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
