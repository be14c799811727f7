"""bench/trace.py on a trace recorded on the chip (``data/``, gzipped):
its numbers against sums taken straight from the trace's events."""
import glob
import gzip
import os

import pytest

from conftest import BENCH
from harness import load_module

trace = load_module("bench_trace", os.path.join(BENCH, "trace.py"))


def pallas(name):
    """Every Pallas call: the older recorded trace names its kernels
    ``checkpoint.<n>`` and the like, the newer ``kernel.<name>.<n>``."""
    return 'custom_call_target="tpu_custom_call"' in name


FIXTURES = sorted(glob.glob(os.path.join(BENCH, "tests", "data",
                                         "*.xplane.pb.gz")))


def _events(path):
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    ops = []
    for plane in trace._device_planes(pd):
        ops.append([(e.name, e.start_ns, e.end_ns) for line in plane.lines
                    if line.name == trace.OPS_LINE for e in line.events])
    spans = sorted(((e.name, e.start_ns, e.end_ns) for plane in pd.planes
                    if not plane.name.startswith("/device:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith(trace.SPAN_PREFIX)),
                   key=lambda s: s[1])
    return ops, spans


@pytest.fixture(scope="module", params=FIXTURES,
                ids=[os.path.basename(p) for p in FIXTURES])
def recorded(request):
    ops, spans = _events(request.param)
    return ops, spans, trace.reduce_events(ops, spans)


def _inside(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if min(e, hi) > max(s, lo)]


def test_there_is_a_recorded_trace():
    assert FIXTURES


def test_busy_is_the_sum_of_the_outermost_operations(recorded):
    ops, spans, red = recorded
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    for evs, busy in zip(ops, red["busy_per_device"]):
        evs = sorted(_inside(evs, lo, hi), key=lambda e: (e[1], -e[2]))
        # on one line, operations nest or follow each other
        total, end = 0, None
        for _, s, e in evs:
            if end is None or s >= end:
                total += e - s
                end = e
        assert busy == pytest.approx(total * 1e-9)
        assert 0 < busy <= red["window_s"]


def test_kernel_and_collective_seconds_are_sums_over_their_events(recorded):
    ops, spans, red = recorded
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    n = len(ops)

    def total(match):
        return sum((e - s) * 1e-9 for evs in ops
                   for name, s, e in _inside(evs, lo, hi) if match(name)) / n

    assert trace.kernel_seconds(red, pallas) == pytest.approx(total(pallas))
    assert total(pallas) > 0
    coll = total(lambda name: bool(trace.COLLECTIVE.match(
        trace.opcode(name))))
    assert red["collective_s"] == pytest.approx(coll)
    # a collective on the ops line runs while nothing else does there
    assert red["exposed_collective_s"] == pytest.approx(coll)
    if n > 1:
        assert coll > 0


def test_idle_gaps_are_attributed_to_the_open_span(recorded):
    ops, spans, red = recorded
    names = {s[0] for s in spans} | {"none"}
    assert {g[0] for g in red["idle_gaps"]} <= names
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9, abs=1e-12)
    assert red["span_counts"]["bench.step"] >= 1
