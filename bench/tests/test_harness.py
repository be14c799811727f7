"""The harness finds every cell's files by name, takes new files with no
code change, and refuses to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

from conftest import BENCH, ROOT, make_root

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files(cell):
    res = harness.resolve(ROOT, cell)
    assert res["mc"]["arch"]
    assert res["traffic"]["clients"] >= res["traffic"]["priority"] > 0
    assert set(res["limits"]) >= {"loss_gap", "gates_diff", "delta1_gap",
                                  "change3_gap"}
    assert {m["name"] for m in res["end_to_end"]} >= {"setup_s"}
    assert res["per_layer"] and all(callable(res["readers"][m["name"]])
                                    for m in res["per_layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_program_config_changes_only_the_reduced_keys(cell):
    from repro.configs import get_config
    res = harness.resolve(ROOT, cell)
    conf = {c["name"]: c for c in SPEC["configs"]}[res["cell"]["config"]]
    mc, keys = res["mc"], res["family"].KEYS
    ours = harness.model_config(res["family"], mc)
    base = get_config(mc["arch"])
    changed = {keys[f] for f in keys if getattr(ours, f) != getattr(base, f)}
    # a key where the program's preset departs from the source is set as
    # published, and the file says so; it is no cut
    corrected = set(mc.get("over_program_preset", {}))
    assert not corrected & set(conf["reduced"])
    assert changed <= (set(conf["reduced"]) | corrected
                       | {"param_dtype", "compute_dtype"})
    assert set(mc["reduced"]) == set(conf["reduced"])


def test_new_config_traffic_and_metric_files_are_found(tmp_path):
    root = make_root(tmp_path)
    with open(os.path.join(root, "bench/metrics/extra_count.py"), "w") as f:
        f.write("def read(ctx):\n    return 1.0\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["per_layer"].append({"name": "extra_count", "unit": "1",
                              "better": "lower", "source": "program_counter",
                              "layer": "device", "moves": "round_s",
                              "workloads": ["tiny.cell"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    res = harness.resolve(root, "tiny.cell")
    assert res["mc"]["hidden_size"] == 64
    assert res["traffic"]["seq"] == 32
    assert res["readers"]["extra_count"]({}) == 1.0
    assert "extra_count" not in harness.resolve(ROOT, CELLS[0])["readers"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_a_run_off_the_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_a_checkout_with_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        harness.peaks_for(BENCH, "TPU v9 imaginary")
    assert harness.peaks_for(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    for w in SPEC["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           w["name"] + ".json"))


def test_compiles_inside_a_window_are_counted():
    import jax
    import jax.numpy as jnp
    with harness._Compiles() as c:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert c.n >= 1
    with harness._Compiles() as c:
        pass
    assert c.n == 0
