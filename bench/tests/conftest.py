import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "arch": "qwen1.5-0.5b", "family": "dense",
    "source": "https://huggingface.co/Qwen/Qwen1.5-0.5B",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "tie_word_embeddings": True, "attention_bias": True,
    "hidden_act": "silu", "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "param_dtype": "float32", "compute_dtype": "float32",
}
TINY_TRAFFIC = {
    "clients": 4, "priority": 2, "per_client": 2,
    "seq": 32, "local_steps": 2, "epsilon": 0.5, "misalign_max": 1.0,
    "lr": 0.05, "aggregator": "mean", "wire_codec": "identity",
    "pool_sequences": 8, "trace_rounds": 2,
}
# sound CPU runs in float32 read ~1e-6 on every number
TINY_LIMITS = {"loss_gap": 1e-3, "gates_diff": 0, "delta1_gap": 1e-2,
               "change3_gap": 1e-2}


def make_root(tmp, cells=("tiny.cell",), config=None, traffic=None,
              limits=None):
    """A checkout-like directory: BENCHMARK.json and bench/ copied, plus a
    tiny configuration, traffic mix and limits for each cell in
    ``cells``."""
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": TINY_CONFIG["source"],
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test size"})
    with open(os.path.join(root, "bench/configs/tiny.json"), "w") as f:
        json.dump(config or TINY_CONFIG, f)
    with open(os.path.join(root, "bench/traffic/tiny.json"), "w") as f:
        json.dump(traffic or TINY_TRAFFIC, f)
    for name in cells:
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": "tiny", "chips": 1,
                                  "why": "test size"})
        with open(os.path.join(root, f"bench/limits/{name}.json"), "w") as f:
            json.dump(limits or TINY_LIMITS, f)
        for m in spec["per_layer"]:
            m.setdefault("workloads", []).append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
