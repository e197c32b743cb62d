"""The plain reference agrees with the system where both compute in
float32, and its STAR softmax is the system's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.correct import reference_decoder as ref
from bench.run import system_config
from bench.tests import arch

NAMES = ["tiny-dense", "tiny-moe", "tiny-dense-bias"]


@pytest.mark.parametrize("name", NAMES)
def test_reference_logits_match_the_system_in_float32(name):
    from repro import ops
    from repro.models.registry import build_model

    config = arch.config(name)
    config["model"]["compute_dtype"] = "float32"
    mod = arch.module(config)
    cfg = system_config(config, mod)
    d = mod.dims_of(config["model"])
    params = weights.make_params(mod.layout(d), 11)
    tokens = np.random.default_rng(0).integers(0, d["V"], 70).astype(np.int32)
    with ops.use(attention="reference"), jax.default_matmul_precision("highest"):
        want = np.asarray(build_model(cfg).forward(params, jnp.asarray(tokens)[None])[0])
    got = np.asarray(mod.logits(params, tokens, d))
    np.testing.assert_allclose(got, want[:, : d["V"]], rtol=2e-4, atol=2e-4)


def test_star_softmax_matches_the_system_engine():
    from repro.core.fixedpoint import FixedPointFormat
    from repro.core.star_softmax import star_softmax

    x = jax.random.normal(jax.random.PRNGKey(3), (6, 300)) * 4.0
    mask = jnp.arange(300)[None, :] < jnp.array([300, 1, 17, 250, 299, 64])[:, None]
    want = star_softmax(x, FixedPointFormat(6, 2), where=mask, mode="gather")
    got = ref.star_softmax(x, mask, 6, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


def test_lower_precision_moves_the_logits():
    config = arch.config("tiny-dense")
    d = ref.dims_of(config["model"])
    params = weights.make_params(ref.layout(d), 2)
    tokens = np.arange(40, dtype=np.int32)
    hi = np.asarray(ref.logits(params, tokens, d))
    lo = np.asarray(ref.logits(params, tokens, d, compute="float8_e4m3fn"))
    rel = np.linalg.norm(hi - lo, axis=-1) / np.linalg.norm(hi, axis=-1)
    assert rel.max() > 1e-2


def test_weights_have_the_system_layout():
    from bench.run import check_layout

    for name in NAMES:
        config = arch.config(name)
        mod = arch.module(config)
        params = weights.make_params(mod.layout(mod.dims_of(config["model"])), 1)
        check_layout(system_config(config, mod), params)
    with pytest.raises(SystemExit):
        check_layout(dataclasses.replace(system_config(config, mod), d_ff=16), params)
