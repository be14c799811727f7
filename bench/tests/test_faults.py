"""A run with the timed path broken underneath comes out not correct.

Each test drives the harness through a whole run of a tiny cell on the
CPU, past its look for a chip, with one fault planted in the compiled
round or in the program beneath it."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

import harness
from faults import answer_altered, half_batch, unchanged

from conftest import BENCH, ROOT, make_root


def _run(root, fault=None, cell="tiny.cell", seed=2147483659):
    res = harness.resolve(root, cell)
    return harness.run(res, seed, 0.2, False, t_start=time.perf_counter(),
                       devices=jax.devices()[:res["cell"]["chips"]],
                       fault=fault)


def test_a_sound_run_is_correct(tiny_root):
    out = _run(tiny_root)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"loss_gap", "gates_diff", "delta1_gap",
                                  "change3_gap"}


@pytest.mark.parametrize("fault", [unchanged, half_batch, answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_a_broken_step_is_not_correct(tiny_root, fault):
    out = _run(tiny_root, fault)
    assert not out["correct"], out["checks"]


def test_half_batch_of_one_row_is_not_correct(tmp_path):
    # one row a client, as the cross-device cell has: the fault leaves out
    # the second half of the row's positions
    from conftest import TINY_TRAFFIC
    root = make_root(tmp_path, traffic=dict(TINY_TRAFFIC, per_client=1))
    assert _run(root)["correct"]
    out = _run(root, half_batch)
    assert not out["correct"], out["checks"]


CHILD = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import jax
from jax.sharding import PartitionSpec as P
import harness
from repro.kernels import ops


def no_exchange(updates, weights, gates):
    # each chip reduces its own clients and nothing crosses chips: the
    # replicated result is the first chip's partial mean
    mesh = jax.sharding.get_abstract_mesh()
    def body(u, w, g):
        wg = (w * g).astype(u.dtype)
        return (wg[:, None] * u).sum(0) / jax.numpy.maximum(wg.sum(), 1e-30)
    return jax.shard_map(body, mesh=mesh, in_specs=(P("data"),) * 3,
                         out_specs=P(), check_vma=False)(updates, weights,
                                                         gates)


if {broken}:
    ops._fedagg_jnp = no_exchange
res = harness.resolve({root!r}, "tiny.cell4")
out = harness.run(res, 2147483659, 0.2, False, t_start=time.perf_counter(),
                  devices=jax.devices()[:4])
print(json.dumps(out["checks"]))
print(json.dumps(out["correct"]))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_the_exchange_between_chips(tmp_path, broken):
    from conftest import TINY_TRAFFIC
    root = make_root(tmp_path, cells=("tiny.cell4",),
                     traffic=dict(TINY_TRAFFIC, clients=8, per_client=1,
                                  local_steps=1))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for w in spec["workloads"]:
        if w["name"] == "tiny.cell4":
            w["chips"] = 4
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    code = CHILD.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                        tests=os.path.join(BENCH, "tests"), root=root,
                        broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    correct = json.loads(p.stdout.strip().splitlines()[-1])
    assert correct is (not broken), p.stdout[-2000:]
