"""The seam between the harness and a model family
(``bench/families/<family>.py``).

The dense family holds what ``bench/reference.py`` and ``bench/flops.py``
held for the dense decoder before families were files, unchanged: the
numbers pinned here were read from that code, and every layout, weight,
count, loss and reader value must come out bit for bit the same. A new
family is one new file, found by the name that a configuration gives."""
import gzip
import hashlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness
import reference
from traffic.generator import RowDraws, federation

from conftest import BENCH, TINY_CONFIG, TINY_TRAFFIC, make_root

SEED = 2147483659
CONFIGS = {c: json.load(open(os.path.join(BENCH, "configs", c + ".json")))
           for c in ("phi3-mini-3.8b", "qwen1.5-0.5b")}
TRAFFIC = {"phi3-mini-3.8b": "silo", "qwen1.5-0.5b": "xdevice"}
PINNED = {
    "phi3-mini-3.8b": {
        "shapes": "75ed04d983be7445fce5c3418132ad20"
                  "76dc322ec03ab4c40c56d59d5256c616",
        "init": "2800c0afad1fc7d0ff87df3199e4dcf1"
                "ac2af3f1dea927b4a14b6efc8cb7a42f",
        "n_params": 536761344, "n_matmul": 438239232,
        # fwd flops, bwd flops, fwd bytes, bwd bytes at (per_client, seq)
        "attn": ["0x1.2048000000000p+35", "0x1.2048000000000p+36",
                 "0x1.2180000000000p+27", "0x1.2180000000000p+28"],
        # round_work at 3 trained clients and at all, every client trained
        "round_work": {
            3: ["0x1.32de1e0000000p+45", "0x1.8000000000000p+13",
                "0x1.0541400000000p+40", "0x1.065c000000000p+32"],
            4: ["0x1.82eb8a0000000p+45", "0x1.0000000000000p+14",
                "0x1.0541400000000p+40", "0x1.065c000000000p+32"]}},
    "qwen1.5-0.5b": {
        "shapes": "80ba72ef4a1a693eed35d6da363cca16"
                  "c724d8f80ef4de41371a6746912c5ffe",
        "init": "8432ea9057817e870b7b58f50744cd33"
                "9b37a4a0ae33c0e35ebc36b93ce70d93",
        "n_params": 463987712, "n_matmul": 463863808,
        "attn": ["0x1.80c0000000000p+33", "0x1.80c0000000000p+34",
                 "0x1.8300000000000p+26", "0x1.8300000000000p+27"],
        "round_work": {
            3: ["0x1.ff32c00000000p+42", "0x1.8000000000000p+10",
                "0x1.8cc6000000000p+38", "0x1.8f18000000000p+31"],
            8: ["0x1.d499300000000p+43", "0x1.0000000000000p+12",
                "0x1.8cc6000000000p+38", "0x1.8f18000000000p+31"]}},
}
WORK = ("model_flops", "trained_tokens", "attn_flops", "attn_bytes")


def _family(mc):
    return harness.family_module(BENCH, mc, "config")


def _sha(items):
    h = hashlib.sha256()
    for k, v in items:
        h.update(k.encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


def _digest(x):
    """Two sums of a leaf's bits, wrapping in uint32: exact, in any
    order."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                     jnp.uint32).ravel()
    i = jnp.arange(b.size, dtype=jnp.uint32)
    return jnp.stack([jnp.sum(b), jnp.sum(b * (2 * i + 1))])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_layout_and_counts_are_the_parents(name):
    mc, pin = CONFIGS[name], PINNED[name]
    fam = _family(mc)
    shapes = sorted([k, list(s), kind]
                    for k, (s, kind) in fam.param_shapes(mc).items())
    assert hashlib.sha256(json.dumps(shapes).encode()).hexdigest() == \
        pin["shapes"]
    assert fam.n_params(mc) == pin["n_params"]
    assert fam.n_matmul(mc) == pin["n_matmul"]
    tr = json.load(open(os.path.join(BENCH, "traffic",
                                     TRAFFIC[name] + ".json")))
    att, b, S = fam.attention(mc), tr["per_client"], tr["seq"]
    assert [flops.attn_fwd_flops(att, b, S).hex(),
            flops.attn_bwd_flops(att, b, S).hex(),
            flops.attn_fwd_bytes(att, b, S).hex(),
            flops.attn_bwd_bytes(att, b, S).hex()] == pin["attn"]
    for trained, want in pin["round_work"].items():
        w = flops.round_work(fam, mc, tr, trained, train_calls=tr["clients"])
        assert [float(w[k]).hex() for k in WORK] == want


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_from_a_seed_are_the_parents(name):
    # leaf by leaf through init_flat, so that one leaf is in memory at once
    mc = CONFIGS[name]
    shapes = _family(mc).param_shapes(mc)
    key = reference.seed_key(SEED)
    leaves = []
    for path in sorted(shapes):
        one = types.SimpleNamespace(
            param_shapes=lambda mc, path=path: {path: shapes[path]})
        fn = jax.jit(lambda k, one=one, path=path: _digest(
            reference.init_flat(one, mc, k)[path]))
        leaves.append((path, np.asarray(fn(key))))
    assert _sha(leaves) == PINNED[name]["init"]


def test_tiny_loss_and_round_are_the_parents():
    mc, tr = TINY_CONFIG, TINY_TRAFFIC
    fam = _family(mc)
    key = reference.seed_key(SEED)
    fed = federation(tr, mc["vocab_size"], SEED)
    draws = RowDraws(SEED)
    client = draws.integers(0, tr["pool_sequences"],
                            (tr["clients"], tr["per_client"]))
    server = draws.integers(0, 64, (tr["per_client"],))
    params = reference.make_init(fam, mc)(key)
    assert _sha(sorted((k, np.asarray(v)) for k, v in
                       reference.flatten(params).items())) == (
        "590bbe13cc68357bf0f59570d09b1e064bd3ef7962479293418fa7365377716b")
    batch = reference.split_rows(fed["test_tokens"][:2])
    with jax.default_matmul_precision("highest"):
        loss = float(jax.jit(lambda p, b: fam.loss(mc, "f32", p, b))(
            params, batch))
    assert loss.hex() == "0x1.623c440000000p+2"
    ref = reference.Reference(fam, mc, tr)
    params, obs = ref.round(params, fed, (client, server))
    assert float(obs["server_loss"]).hex() == "0x1.64b9280000000p+2"
    assert [float(x).hex() for x in obs["local_losses"]] == [
        "0x1.640eae0000000p+2", "0x1.631c0e0000000p+2",
        "0x1.63e7340000000p+2", "0x1.62ca080000000p+2"]
    assert list(obs["gates"]) == [1.0, 1.0, 1.0, 1.0]
    assert _sha(sorted((k, np.asarray(v)) for k, v in
                       reference.flatten(params).items())) == (
        "710a29a0f1d46af15402548637c5d5e85a6dd948f12ef89902042b2316e02347")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    trace = harness.load_module("bench_trace",
                                os.path.join(BENCH, "trace.py"))
    xplane = tmp_path_factory.mktemp("trace") / "window.xplane.pb"
    with gzip.open(os.path.join(BENCH, "tests", "data",
                                "phi3-silo-6rounds-phases.xplane.pb.gz"),
                   "rb") as f:
        xplane.write_bytes(f.read())
    return trace, trace.reduce(str(xplane))


@pytest.mark.parametrize("metric,value", [
    ("round_mfu", "0x1.b5c57aef5af8ap+4"),
    ("flash_attn_roofline", "0x1.98e3b5e18022ap+0")])
def test_readers_read_the_recorded_trace_as_the_parent(recorded, metric,
                                                       value):
    trace, red = recorded
    mc = CONFIGS["phi3-mini-3.8b"]
    ctx = {"mc": mc, "family": _family(mc),
           "traffic": json.load(open(os.path.join(BENCH, "traffic",
                                                  "silo.json"))),
           "rounds": [{"gates": [1.0, 1.0, 1.0, 0.0]}]
           * red["span_counts"]["bench.step"],
           "trace": red, "mode": "temporal", "chips": 1,
           "peaks": harness.peaks_for(BENCH, "TPU v5 lite"), "flops": flops,
           "kernel_seconds": trace.kernel_seconds}
    assert float(harness.metric_reader(BENCH, metric)(ctx)).hex() == value


# the rule the flash reader used before the kernels had names: every Pallas
# call but the one whose output is a single [1, M] row (fedagg)
_PALLAS = 'custom_call_target="tpu_custom_call"'


def _by_output_shape(name):
    return _PALLAS in name and not re.match(
        r"^%?[\w.\-]+ = \w+\[1,\d+\]\{[^}]*\} custom-call\(", name)


def test_flash_kernels_are_picked_by_name(recorded):
    trace, red = recorded
    is_flash = harness.load_module("m_flash", os.path.join(
        BENCH, "metrics", "flash_attn_roofline.py")).is_flash
    ops = red["op_seconds"]
    assert {k for k in ops if is_flash(k)} == \
        {k for k in ops if _by_output_shape(k)}
    assert trace.kernel_seconds(red, is_flash) == \
        trace.kernel_seconds(red, _by_output_shape)
    moe = ("%kernel.moe_gmm.4 = bf16[512,1408]{1,0} custom-call(bf16[512,"
           "2048]{1,0} %x, bf16[8,2048,1408]{2,1,0} %w), " + _PALLAS)
    assert _by_output_shape(moe) and not is_flash(moe)
    with_moe = dict(red, op_seconds=dict(ops, **{moe: 0.5}))
    assert trace.kernel_seconds(with_moe, is_flash) == \
        trace.kernel_seconds(red, is_flash)


# a family of one tied embedding and a stack of square matrices, each
# followed by tanh; no attention
TOY = '''
import jax
import jax.numpy as jnp
from reference import Arith

KEYS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
        "vocab_size": "vocab_size"}
LISTS = ("blocks",)


def require(mc):
    if mc.get("act", "tanh") != "tanh":
        raise SystemExit("the toy family is tanh only")


def param_shapes(mc):
    d, V, L = mc["hidden_size"], mc["vocab_size"], mc["num_hidden_layers"]
    out = {"embed": ((V, d), "embed")}
    out.update({f"blocks.{i}.w": ((d, d), "matrix") for i in range(L)})
    return out


def loss(mc, precision, params, batch):
    ar = Arith(precision)
    x = params["embed"][batch["tokens"]]
    for blk in params["blocks"]:
        x = jnp.tanh(ar.mm(x, blk["w"]))
    logits = ar.mm(x, params["embed"].T).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def n_matmul(mc):
    d = mc["hidden_size"]
    return mc["num_hidden_layers"] * d * d + d * mc["vocab_size"]


def n_params(mc):
    return n_matmul(mc)


def attention(mc):
    return {"heads": 0, "kv_heads": 0, "d_qk": 0, "d_v": 0, "layers": 0}
'''
TOY_CONFIG = {"arch": "toy", "family": "toy", "hidden_size": 16,
              "num_hidden_layers": 3, "vocab_size": 64}


def test_a_new_family_is_one_new_file(tmp_path):
    root = make_root(tmp_path, config=TOY_CONFIG)
    with open(os.path.join(root, "bench/families/toy.py"), "w") as f:
        f.write(TOY)
    res = harness.resolve(root, "tiny.cell")
    fam, mc = res["family"], res["mc"]
    assert fam.KEYS["d_model"] == "hidden_size"
    fam.require(mc)
    params = reference.make_init(fam, mc)(reference.seed_key(SEED))
    assert [b["w"].shape for b in params["blocks"]] == [(16, 16)] * 3
    assert params["embed"].shape == (64, 16)
    assert sorted(reference.flatten(params)) == sorted(fam.param_shapes(mc))
    tr = res["traffic"]
    w = flops.round_work(fam, mc, tr, trained=3)
    tokens = tr["per_client"] * tr["seq"]
    assert w["model_flops"] == (3 * tr["local_steps"] * tokens * 6.0
                                * (3 * 16 * 16 + 16 * 64)
                                + (tr["clients"] + 1) * tokens * 2.0
                                * (3 * 16 * 16 + 16 * 64))
    assert w["attn_flops"] == 0
    fed = federation(tr, mc["vocab_size"], SEED)
    draws = RowDraws(SEED)
    client = draws.integers(0, tr["pool_sequences"],
                            (tr["clients"], tr["per_client"]))
    server = draws.integers(0, 64, (tr["per_client"],))
    _, obs = reference.Reference(fam, mc, tr).round(params, fed,
                                                    (client, server))
    assert np.isfinite(obs["server_loss"])
    assert np.all(np.isfinite(obs["local_losses"]))


@pytest.mark.parametrize("family", ["no_such_family", None])
def test_a_configuration_without_its_family_file_exits(tmp_path, family):
    config = dict(TINY_CONFIG, family=family)
    if family is None:
        del config["family"]
    root = make_root(tmp_path, config=config)
    with pytest.raises(SystemExit) as e:
        harness.resolve(root, "tiny.cell")
    assert "bench/configs/tiny.json" in str(e.value)
    assert "'dense'" in str(e.value)
