"""The control comes out not correct: the reference computed one step below
a precision that the configuration states, put in the program's place, at
a size a test run can hold (the chip readings at the cells' own sizes are
in PERF.md, from ``bench/control.py``)."""
import json
import os

import jax
import pytest

import check
import harness

from conftest import BENCH, TINY_CONFIG, make_root

CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "limits")))


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    # the configuration's precisions: float32 weights, bfloat16 compute
    root = make_root(tmp_path_factory.mktemp("ctl"),
                     config=dict(TINY_CONFIG, compute_dtype="bfloat16"))
    sess = harness.Session(harness.resolve(root, "tiny.cell"),
                           jax.devices()[:1])
    prog = sess.start(2147483659)
    sess.free()
    return sess, prog


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct_and_controls_are_not(session, cell):
    sess, prog = session
    limits = json.load(open(os.path.join(BENCH, "limits", cell + ".json")))
    tol = limits["loss_gap"]
    ref = sess.reference("f32", follow=prog, tol=tol)
    ok, checks = check.judge(check.readings(prog, ref, tol), limits)
    assert ok, checks
    for precision in ("bf16", "fp8"):
        ok, checks = check.judge(
            check.readings(sess.reference(precision), ref, tol), limits)
        assert not ok, (precision, checks)
