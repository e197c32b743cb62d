"""CPU rehearsal of ``chip_smoke.py``: its phase functions at smoke size.

The script itself only runs on a TPU; here its serve phases, the kernel
check on the live pool and the logit parity check run on granite-8b's
smoke config with the Pallas kernel in interpret mode, so a wrong path,
argument or control flow fails before any chip time is spent.
"""

import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.param import materialize
from repro.models.registry import build_model

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model_params():
    cfg = get_smoke_config("granite_8b")
    return cfg, materialize(build_model(cfg).param_specs(), jax.random.PRNGKey(0))


def _phases(smoke):
    return (
        smoke.Phase("A", "fp32", (16, 32, 8, 24), (4, 6, 3, 5), track=(0, 3)),
        smoke.Phase("B", "int8", (24, 40, 32, 24), (5, 4, 6, 3),
                    shared_prefix=16, prefix_cache=True,
                    prefill_chunk_tokens=8, track=(1, 3)),
    )


@pytest.fixture(scope="module")
def served(smoke, model_params):
    """Each rehearsal phase served once: its report and logit readings."""
    cfg, params = model_params
    out = []
    for phase in _phases(smoke):
        rep = smoke.run_phase(cfg, params, phase, pool_blocks=64, slots=2)
        out.append((phase, rep, smoke.check_logits(cfg, params, rep["tracked"])))
    return out


@pytest.mark.parametrize("which", [0, 1])
def test_phase_serves_budgets_and_matches_references(smoke, served, which):
    phase, rep, logits = served[which]
    assert rep["tokens_served"] == sum(phase.gens)
    assert rep["kernel"]["error"] <= smoke.KERNEL_TOL
    if phase.prefix_cache:
        assert rep["prefix_tokens_saved"] > 0
    tracked = rep["tracked"]
    assert [len(t["logits"]) for t in tracked] == [
        phase.gens[i] - 1 for i in phase.track]
    assert logits["error"] <= smoke.LOGITS_TOL
    # the kernel really was the one dispatched, resolved for this platform
    disp = smoke.pallas_dispatches()
    assert any(d["impl"] == "pallas_paged" for d in disp)
    assert all(d["interpret"] for d in disp)  # CPU: interpret mode


@pytest.mark.parametrize("which", [0, 1])
@pytest.mark.parametrize("fault", ["page", "head"])
def test_kernel_check_sees_planted_fault(smoke, served, which, fault):
    """A page of another slot, or a query head reading its neighbour's KV
    head, must move the kernel's output past the check's limit."""
    assert served[which][1]["kernel"][fault] > smoke.KERNEL_TOL


@pytest.mark.parametrize("which", [0, 1])
def test_logit_check_sees_float8_compute(smoke, served, which):
    """Logits computed in float8, below the config's bfloat16, must read
    past the logit check's limit."""
    assert served[which][2]["float8"] > smoke.LOGITS_TOL


def test_separation_refuses_a_blind_check(smoke):
    smoke.require_separation("x", {"error": 1e-3, "page": 0.5}, 1e-2)
    with pytest.raises(AssertionError, match="error"):
        smoke.require_separation("x", {"error": 2e-2, "page": 0.5}, 1e-2)
    with pytest.raises(AssertionError, match="cannot see"):
        smoke.require_separation("x", {"error": 1e-3, "page": 5e-3}, 1e-2)


def test_logit_check_fails_on_wrong_logits(smoke, model_params):
    cfg, params = model_params
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    wrong = {i: rng.normal(size=cfg.vocab_size).astype(np.float32)
             for i in (1, 2)}
    with pytest.raises(AssertionError, match="error"):
        smoke.check_logits(cfg, params, [
            {"tokens": toks, "prompt_len": 9, "logits": wrong}])


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out
