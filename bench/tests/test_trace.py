"""bench/trace.py: the reduction from a profiler trace to the metrics'
numbers, on hand-made events and on a trace recorded on the chip."""
import os

import pytest

from conftest import BENCH
from harness import load_module

trace = load_module("bench_trace", os.path.join(BENCH, "trace.py"))
NS = 1e-9

# one round on two devices: host spans, and each device's operations as the
# TPU trace names them (HLO text; a loop holds the operations it runs)
SPANS = [("bench.build", 0, 100), ("bench.put", 100, 150),
         ("bench.step", 150, 160), ("bench.wait", 160, 900),
         ("bench.read", 900, 1000)]
PALLAS = 'custom_call_target="tpu_custom_call"'
FLASH = ("%kernel.flash_fwd.3 = (bf16[2,1,8,4]{3,2,1,0}, f32[2,1,8]{2,1,0}) "
         "custom-call(bf16[2,1,8,4]{3,2,1,0} %q), " + PALLAS)
FEDAGG = ("%custom-call.9 = f32[1,4096]{1,0} custom-call(f32[2,4096]{1,0} %u,"
          " f32[2,1]{1,0} %w, f32[2,1]{1,0} %g), " + PALLAS)
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
FUSION2 = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
ALLREDUCE = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add"
DONE = "%all-reduce-done.2 = f32[8]{0} all-reduce-done(f32[8]{0} %s)"
LOOP = "%while.5 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
OPS = [
    # device 0: a loop 200-800 that runs a fusion, an all-reduce, the flash
    # kernel and another fusion; fedagg after the loop
    [(LOOP, 200, 800), (FUSION, 200, 350), (ALLREDUCE, 350, 450),
     (FLASH, 450, 600), (FUSION2, 600, 800), (FEDAGG, 820, 850)],
    # device 1: a fusion, a wait on an async all-reduce, the flash kernel
    [(FUSION, 200, 300), (DONE, 300, 700), (FLASH, 700, 760),
     (FEDAGG, 780, 850)],
]


@pytest.fixture(scope="module")
def red():
    return trace.reduce_events(OPS, SPANS)


def test_window_and_busy_union(red):
    assert red["window_s"] == pytest.approx(1000 * NS)
    # device 0: 200-800 and 820-850; device 1: 200-760 and 780-850
    assert red["busy_per_device"] == pytest.approx([630 * NS, 630 * NS])
    assert red["busy_s"] == pytest.approx(630 * NS)


def test_kernel_seconds_by_name(red):
    flash = load_module("m_flash", os.path.join(
        BENCH, "metrics", "flash_attn_roofline.py")).is_flash
    # (150 + 60) / 2 devices; the fedagg call, a Pallas kernel too, is not
    # flash attention's
    assert trace.kernel_seconds(red, flash) == pytest.approx(105 * NS)
    assert not flash(FEDAGG)
    # innermost operations only: the loop's 600 is not a fusion's
    assert trace.kernel_seconds(red, r"^%fusion") == pytest.approx(
        (150 + 200 + 100) / 2 * NS)
    assert trace.kernel_seconds(red, r"no_such_kernel") is None
    labels = dict(red["top_ops"])
    assert labels["kernel.flash_fwd.3 custom-call tpu_custom_call"] == \
        pytest.approx(105 * NS)
    assert "while.5 while" not in labels


def test_exposed_collective_time(red):
    # device 0: the all-reduce 350-450; device 1: the wait 300-700
    assert red["exposed_collective_s"] == pytest.approx((100 + 400) / 2 * NS)


def test_gaps_are_labelled_by_the_open_span(red):
    # each device is idle 0-200 (middle 100: bench.put opens at 100) and
    # 850-1000 (middle 925: bench.read); device 0 also 800-820 and device 1
    # 760-780 (bench.wait)
    assert red["idle_gaps"][0] == ["bench.put", pytest.approx(200 * NS)]
    by_span = red["idle_by_span"]
    assert by_span["bench.put"] == pytest.approx((200 + 200) / 2 * NS)
    assert by_span["bench.read"] == pytest.approx((150 + 150) / 2 * NS)
    assert by_span["bench.wait"] == pytest.approx((20 + 20) / 2 * NS)
    assert sum(by_span.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert red["span_counts"]["bench.step"] == 1
