"""bench/phases.py: the round's phases from its HLO, joined with a trace.

The recorded pair in ``data/`` is one traced window of
``phi3-mini-3.8b.silo`` on a v5e chip (``*-phases.xplane.pb.gz``) and
the compiled round's optimized HLO text of the same run
(``*-phases.hlo.txt.gz``)."""
import glob
import gzip
import os

import pytest

from conftest import BENCH
from harness import load_module, metric_reader

phases = load_module("bench_phases", os.path.join(BENCH, "phases.py"))
trace = load_module("bench_trace", os.path.join(BENCH, "trace.py"))
flash = load_module("m_flash", os.path.join(
    BENCH, "metrics", "flash_attn_roofline.py")).is_flash
XPLANE = sorted(glob.glob(os.path.join(BENCH, "tests", "data",
                                       "*-phases.xplane.pb.gz")))
READERS = ("round_match_ms", "round_train_ms", "round_aggregate_ms",
           "round_server_ms", "round_unscoped_ms")

# a hand-written module: one computation of one phase with an unnamed
# copy, one of mixed phases, and an entry with a kernel inside a phase
HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/while/body/closed_call/fedalign.train/cond/branch_1_fun/transpose(jvp(mul))"}
  ROOT %copy.1 = f32[8]{0} copy(%mul.1)
}

%mixed (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %add.2 = f32[8]{0} add(%q, %q), metadata={op_name="jit(step)/while/body/closed_call/fedalign.aggregate/add"}
  %add.3 = f32[8]{0} add(%add.2, %q), metadata={op_name="jit(step)/while/body/add"}
  ROOT %copy.2 = f32[8]{0} copy(%add.3)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %kernel.flash_fwd.7 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fedalign.eval/jvp(kernel.flash_fwd)/pallas_call"}
  ROOT %copy.3 = f32[8]{0} copy(%kernel.flash_fwd.7), metadata={op_name="jit(step)/fedalign.server_step/copy"}
}
"""


def _reduced(op_seconds, busy):
    return {"op_seconds": op_seconds, "busy_s": busy}


def test_each_instruction_takes_its_innermost_phase_or_its_computations():
    table = phases.instruction_phases(HLO)
    by_name = {k.split(" =")[0].lstrip("%"): v for k, v in table.items()}
    assert by_name["mul.1"] == ("train", None)
    assert by_name["copy.1"] == ("train", None)          # whole computation
    assert by_name["add.2"] == ("aggregate", None)
    assert by_name["add.3"] == (phases.UNSCOPED, None)   # named, no phase
    assert by_name["copy.2"] == (phases.UNSCOPED, None)  # mixed computation
    assert by_name["kernel.flash_fwd.7"] == ("eval", "flash_fwd")
    assert by_name["copy.3"] == ("server_step", None)


def test_trace_operations_join_by_their_text_up_to_the_opcode():
    ops = {
        "%mul.1 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p)": 0.5,
        "%kernel.flash_fwd.7 = f32[8]{0} custom-call(f32[8]{0} %x), "
        'custom_call_target="tpu_custom_call"': 0.25,
        "%copy.2 = f32[8]{0} copy(f32[8]{0} %add.3)": 0.125,
        # another program's instruction of the same name and opcode
        "%mul.1 = f32[4]{0} multiply(f32[4]{0} %a, f32[4]{0} %b)": 0.0625,
    }
    got = phases.phase_seconds(HLO, _reduced(ops, busy=1.0))
    assert got["phases"] == {"train": 0.5, "eval": 0.25,
                             phases.UNSCOPED: 0.25}
    assert got["kernels"] == {"flash_fwd": 0.25}
    assert got["joined_s"] == 0.875 and got["leaf_s"] == 0.9375


def test_readers_give_nothing_for_a_program_without_phases():
    import jax
    import jax.numpy as jnp
    text = jax.jit(lambda x: jnp.sin(x) * 2).lower(
        jnp.ones(8)).compile().as_text()
    ctx = {"hlo": text, "rounds": [{}] * 2,
           "trace": _reduced({"%sine.1 = f32[8]{0} sine(f32[8]{0} %x)": 1.0},
                             busy=1.0)}
    assert phases.phase_seconds(text, ctx["trace"]) is None
    joins = _reduced({"%mul.1 = f32[8]{0} multiply(f32[8]{0} %p, "
                      "f32[8]{0} %p)": 0.5}, busy=1.0)
    for name in READERS:
        assert metric_reader(BENCH, name)(ctx) is None
        assert metric_reader(BENCH, name)(dict(ctx, hlo=HLO)) is None
        assert metric_reader(BENCH, name)(
            dict(ctx, hlo=HLO, trace=joins)) is not None


def test_readers_find_the_traced_program_among_the_loaded_ones():
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("fedalign.train"):
            return jnp.sin(x) * 2

    compiled = jax.jit(step).lower(jnp.ones(8)).compile()
    text = compiled.as_text()
    assert text in phases.loaded_texts()
    trained = [k for k, (p, _) in phases.instruction_phases(text).items()
               if p == "train"]
    assert trained
    ran = _reduced({k + "f32[8]{0} %x)": 0.25 for k in trained}, busy=1.0)
    ctx = {"rounds": [{}] * 2, "trace": ran}
    assert metric_reader(BENCH, "round_train_ms")(ctx) == pytest.approx(
        1e3 * 0.25 * len(trained) / 2)
    # the parent's side: a trace whose operations join no loaded program
    other = _reduced({"%sine.9 = f32[3]{0} sine(f32[3]{0} %y)": 1.0},
                     busy=1.0)
    for name in READERS:
        assert metric_reader(BENCH, name)(dict(ctx, trace=other)) is None


@pytest.fixture(scope="module", params=XPLANE,
                ids=[os.path.basename(p) for p in XPLANE])
def recorded(request, tmp_path_factory):
    path = request.param
    with gzip.open(path.replace(".xplane.pb.gz", ".hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    xplane = tmp_path_factory.mktemp("trace") / "window.xplane.pb"
    with gzip.open(path, "rb") as f:
        xplane.write_bytes(f.read())
    red = trace.reduce(str(xplane))
    rounds = [{}] * red["span_counts"]["bench.step"]
    return {"hlo": hlo, "trace": red, "rounds": rounds}


def test_there_is_a_recorded_pair():
    assert XPLANE


def test_phases_and_unscoped_sum_to_busy(recorded):
    got = phases.phase_seconds(recorded["hlo"], recorded["trace"])
    # the gate's few [C]-sized operations are fused into fusions that
    # other phases name, so the silo cell reads no gate time
    for name in ("server_loss", "eval", "train", "aggregate", "server_step",
                 phases.UNSCOPED):
        assert got["phases"][name] > 0, name
    assert all(v >= 0 for v in got["phases"].values())
    assert sum(got["phases"].values()) == pytest.approx(
        recorded["trace"]["busy_s"], rel=1e-9)


def test_almost_all_leaf_time_joins_an_instruction(recorded):
    got = phases.phase_seconds(recorded["hlo"], recorded["trace"])
    assert got["joined_s"] >= 0.99 * got["leaf_s"]


def test_the_readers_sum_to_busy_ms_per_round(recorded):
    values = {n: metric_reader(BENCH, n)(recorded) for n in READERS}
    per_round = 1e3 * recorded["trace"]["busy_s"] / len(recorded["rounds"])
    assert sum(values.values()) == pytest.approx(per_round, rel=1e-2)
    assert values["round_unscoped_ms"] <= 0.1 * per_round
    assert values["round_train_ms"] == max(values.values())


def test_kernel_scopes_name_the_flash_kernels(recorded):
    got = phases.phase_seconds(recorded["hlo"], recorded["trace"])
    by_scope = sum(s for k, s in got["kernels"].items()
                   if k.startswith("flash_"))
    assert set(got["kernels"]) == {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"}
    # the streamed mean of the silo cell never calls fedagg
    assert "fedagg" not in got["kernels"]
    assert by_scope == pytest.approx(
        trace.kernel_seconds(recorded["trace"], flash), rel=5e-3)
