"""A configuration brings its architecture as a module of its own: the
harness takes the key table, the sizes, the weight layout and the
reference from it, so an architecture that lives only beside the tests
runs through the harness unchanged, and the decoder's runs read as they
did before the harness took it from its module."""

import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from bench import common, run, weights
from bench.correct import reference_decoder
from bench.tests import arch

# make_params at seed 3000000001, as the harness gave it when the
# decoder's layout lived in weights.py: leaf path -> (shape, sha256 of the
# float32 bytes, first 16 hex digits); at full size the shapes alone
PARENT_WEIGHTS = {
    "tiny-dense": {
        "['blocks']['attn']['wk']": ((2, 256, 64), "e40b518cea4e13e7"),
        "['blocks']['attn']['wo']": ((2, 256, 256), "f7e4061271d9574d"),
        "['blocks']['attn']['wq']": ((2, 256, 256), "144442ec987ffac5"),
        "['blocks']['attn']['wv']": ((2, 256, 64), "e01481f6f1044117"),
        "['blocks']['ln1']['scale']": ((2, 256), "fef951e6c76ad6a0"),
        "['blocks']['ln2']['scale']": ((2, 256), "fef951e6c76ad6a0"),
        "['blocks']['mlp']['wg']": ((2, 256, 512), "0eb3d5a8fc1a148a"),
        "['blocks']['mlp']['wi']": ((2, 256, 512), "47664d61ae5c2863"),
        "['blocks']['mlp']['wo']": ((2, 512, 256), "0221be87c29761c0"),
        "['embed']['table']": ((2048, 256), "b81c345ed658ebde"),
        "['final_norm']['scale']": ((256,), "893a106828fbdb95"),
        "['unembed']['kernel']": ((256, 2048), "86615f086dabdad2"),
    },
    "tiny-moe": {
        "['blocks']['attn']['wk']": ((2, 256, 64), "e40b518cea4e13e7"),
        "['blocks']['attn']['wo']": ((2, 256, 256), "f7e4061271d9574d"),
        "['blocks']['attn']['wq']": ((2, 256, 256), "144442ec987ffac5"),
        "['blocks']['attn']['wv']": ((2, 256, 64), "e01481f6f1044117"),
        "['blocks']['ln1']['scale']": ((2, 256), "fef951e6c76ad6a0"),
        "['blocks']['ln2']['scale']": ((2, 256), "fef951e6c76ad6a0"),
        "['blocks']['moe']['router']": ((2, 256, 32), "bfd6ab77214806bd"),
        "['blocks']['moe']['wg']": ((2, 32, 256, 64), "d4084a0cbc7dbdd5"),
        "['blocks']['moe']['wi']": ((2, 32, 256, 64), "21a446b3583894da"),
        "['blocks']['moe']['wo']": ((2, 32, 64, 256), "b2414d619bda0701"),
        "['embed']['table']": ((2048, 256), "e4721c8bf269451f"),
        "['final_norm']['scale']": ((256,), "893a106828fbdb95"),
        "['unembed']['kernel']": ((256, 2048), "bf615bff5514d27b"),
    },
    "granite-8b-d8": {
        "['blocks']['attn']['wk']": (8, 4096, 1024),
        "['blocks']['attn']['wo']": (8, 4096, 4096),
        "['blocks']['attn']['wq']": (8, 4096, 4096),
        "['blocks']['attn']['wv']": (8, 4096, 1024),
        "['blocks']['ln1']['scale']": (8, 4096),
        "['blocks']['ln2']['scale']": (8, 4096),
        "['blocks']['mlp']['wg']": (8, 4096, 14336),
        "['blocks']['mlp']['wi']": (8, 4096, 14336),
        "['blocks']['mlp']['wo']": (8, 14336, 4096),
        "['embed']['table']": (49152, 4096),
        "['final_norm']['scale']": (4096,),
        "['unembed']['kernel']": (4096, 49152),
    },
}
# the parent's table: system_config put each key the file states into the
# system's own config under this field, and nothing else
PARENT_MODEL_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "num_local_experts": "num_experts",
    "num_experts_per_tok": "top_k", "capacity_factor": "capacity_factor",
    "param_dtype": "param_dtype", "compute_dtype": "compute_dtype",
    "family": "family",
}


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def _leaves(tree):
    return {jax.tree_util.keystr(k): a
            for k, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", arch.every_config())
def test_every_configuration_names_a_module_that_maps_its_keys(name):
    config = arch.config(name)
    mod = arch.module(config)
    for attr in ("FIELDS", "dims_of", "layout", "logits"):
        assert hasattr(mod, attr), (name, attr)
    assert set(config["model"]) <= set(mod.FIELDS)
    dims = mod.dims_of(config["model"])
    assert dims["V"] == config["model"]["vocab_size"]
    assert jax.tree.leaves(mod.layout(dims), is_leaf=weights._is_leaf)


@pytest.mark.parametrize("key, value", [("sliding_window", 16),
                                        ("attention_bias", True)])
def test_a_key_the_table_does_not_map_stops_the_run(key, value):
    """The decoder states neither a window nor biases, though the system
    serves both: the run stops rather than serve another model."""
    config = arch.config("tiny-dense")
    config["model"][key] = value
    with pytest.raises(SystemExit, match="does not run tiny-dense as stated"):
        run.system_config(config, arch.module(config))


@pytest.mark.parametrize("name", ["granite-8b-d8", "granite-moe-1b-a400m",
                                  "tiny-dense", "tiny-moe"])
def test_system_config_is_what_it_was(name):
    """The ModelConfig the parent's table built: the system's own config
    with the file's sizes put in, so a field the system adds later reads
    the same on both sides."""
    from repro.configs import get_config

    config = arch.config(name)
    model = config["model"]
    want = dataclasses.replace(get_config(config["system_arch"]), **{
        PARENT_MODEL_FIELDS[k]: v for k, v in model.items() if k in PARENT_MODEL_FIELDS})
    assert run.system_config(config, arch.module(config)) == want


@pytest.mark.parametrize("name", sorted(PARENT_WEIGHTS))
def test_decoder_weights_are_bit_identical_to_what_they_were(name):
    config = arch.config(name)
    mod = arch.module(config)
    tree = mod.layout(mod.dims_of(config["model"]))
    dtype = config["model"]["param_dtype"]
    want = PARENT_WEIGHTS[name]
    if name.startswith("tiny"):
        got = _leaves(weights.make_params(tree, 3000000001, dtype))
        assert {k: (a.shape, _digest(np.asarray(a).tobytes()))
                for k, a in got.items()} == want
    else:  # full size: shapes alone, nothing made
        got = _leaves(jax.eval_shape(lambda: weights.make_params(tree, 3000000001, dtype)))
        assert {k: a.shape for k, a in got.items()} == want
    assert {str(a.dtype) for a in got.values()} == {dtype}


def test_zeros_and_fixed_deviation_inits():
    tree = {"z": ((3, 5), "zeros"), "n": ((2, 20000), "normal:0.25"),
            "f": ((4, 400, 3), "fan_in")}
    p = weights.make_params(tree, 5)
    assert not np.asarray(p["z"]).any()
    assert np.std(np.asarray(p["n"])) == pytest.approx(0.25, rel=0.02)
    assert np.std(np.asarray(p["f"])) == pytest.approx(400 ** -0.5, rel=0.05)
    with pytest.raises(ValueError, match="unknown init"):
        weights.make_params({"x": ((2,), "uniform")}, 5)


def _run_bias_cell(module, seed=3000000001):
    config = arch.config("tiny-dense-bias")
    bench = common.load_json(arch.DATA / "tiny-benchmark.json")
    bench["workloads"] = [{"name": "tiny-dense-bias.offline", "config": config["name"],
                           "traffic": "tiny-offline", "chips": 1, "why": "test"}]
    opts = run.Options(require_tpu=False, config=config, architecture=module,
                       benchmark=bench,
                       mix=common.load_json(arch.DATA / "tiny-offline.json"))
    args = run.parse_args(["--workload", "tiny-dense-bias.offline", "--seed",
                           str(seed), "--seconds", "6", "--trace", "0"])
    return run.run_cell(args, opts)["line"]


def test_an_architecture_that_lives_beside_the_tests_runs_correct():
    line = _run_bias_cell(arch.module(arch.config("tiny-dense-bias")))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"itl_p95_s", "setup_s"}
    assert line["checks"]["window_compiles"]["value"] == 0


def test_its_reference_without_the_biases_is_not_correct():
    """The same run, its reference planted with the decoder's attention,
    which drops the biases: the check sees the new parameters."""
    twin = arch.module(arch.config("tiny-dense-bias"))  # a copy of its own
    twin._attention = reference_decoder._attention
    line = _run_bias_cell(twin)
    assert line["correct"] is False
    assert line["checks"]["token_gap"]["value"] > line["checks"]["token_gap"]["limit"]
