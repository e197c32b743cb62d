"""``correct`` sees the faults a serving cell can have: the whole run is
driven on the CPU at a small size (the look for a chip skipped), with the
timed path broken underneath, and must come out not correct."""

import numpy as np
import pytest

from bench import common, run
from bench.control import CONTROL

DATA = common.BENCH / "tests" / "data"
CELL = "tiny-dense.offline"


CELLS = {"tiny-dense.chat": ("tiny-dense", "tiny-chat"),
         "tiny-dense.offline": ("tiny-dense", "tiny-offline")}


def run_tiny(on_engine=None, seed=3000000001, control=None, cell=CELL):
    config, mix = CELLS[cell]
    opts = run.Options(
        require_tpu=False,
        config=common.load_json(DATA / f"{config}.json"),
        benchmark=common.load_json(DATA / "tiny-benchmark.json"),
        mix=common.load_json(DATA / f"{mix}.json"),
        control=control, on_engine=on_engine)
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", "6", "--trace", "0"])
    return run.run_cell(args, opts)


@pytest.mark.parametrize("seed", [3000000001, 7, 123456])
def test_float8_control_in_the_system_place_is_not_correct(seed):
    """The reference in float8 (e4m3), one step below the bfloat16 the
    configurations state, put in the system's place and judged as a run
    is: its widest gap passes the limit that the system's own tokens, on
    the same sample, stay under."""
    out = run_tiny(control=CONTROL, seed=seed)
    check = out["line"]["checks"]["token_gap"]
    assert out["line"]["correct"] is False
    assert check["value"] > check["limit"] > max(out["info"]["system_gaps"])


@pytest.mark.parametrize("cell, metrics", [
    ("tiny-dense.chat", {"ttft_p95_s", "itl_p95_s", "setup_s"}),
    ("tiny-dense.offline", {"itl_p95_s", "output_tokens_per_s", "setup_s"}),
])
def test_sound_run_is_correct_and_reports_its_metrics(cell, metrics):
    out = run_tiny(cell=cell)
    line = out["line"]
    assert line["correct"] is True
    assert set(line["metrics"]) == metrics
    assert list(line)[-1] == "checks"
    assert line["checks"]["window_compiles"]["value"] == 0
    assert line["attempted"] > 0 and out["info"]["window_ticks"] > 0


def test_token_altered_where_it_is_produced_is_not_correct():
    def alter(eng):
        tick = eng._tick

        def wrong(*args):  # the decode tick samples the next id over
            sampled, last, pool = tick(*args)
            return (sampled + 1) % eng.cfg.vocab_size, last, pool

        wrong._cache_size = tick._cache_size  # the engine counts its programs
        eng._tick = wrong

    line = run_tiny(alter)["line"]
    assert line["correct"] is False
    assert line["checks"]["token_gap"]["value"] > line["checks"]["token_gap"]["limit"]


def test_decode_step_that_returns_its_cache_unchanged_is_not_correct():
    def freeze(eng):
        step = eng.model.decode_step_paged

        def unchanged(params, cache, tokens, tables, *, cache_t):
            logits, new = step(params, cache, tokens, tables, cache_t=cache_t)
            return logits, {**new, "layers": cache["layers"]}

        eng.model.decode_step_paged = unchanged
        eng._tick = eng._build_tick()

    line = run_tiny(freeze)["line"]
    assert line["correct"] is False


def test_compile_inside_the_window_is_not_correct():
    def late_shape(eng):  # once warm, every tick runs a program never seen
        import os

        import jax
        import jax.numpy as jnp

        step, seen = eng.step, [0]
        # a constant of this run's own, so no earlier run's persistent
        # cache entry can serve the program
        salt = float(int.from_bytes(os.urandom(3), "little"))

        def step_and_compile():
            if eng.ticks > 8:  # past the warm-up's few ticks
                seen[0] += 1
                jax.jit(lambda x: x * salt)(jnp.zeros(seen[0])).block_until_ready()
            return step()

        eng.step = step_and_compile

    line = run_tiny(late_shape)["line"]
    assert line["checks"]["window_compiles"]["value"] > 0
    assert line["correct"] is False


def test_moe_prefill_needs_a_program_per_prompt_length():
    """Why the MoE configuration has no cell: its prefill program is keyed
    by the expert capacity of the whole prompt, so the offline mix's
    unrounded prompts reach a prefill program per prompt length, where
    the dense configuration needs 68 in all."""
    from types import SimpleNamespace

    from repro.models.registry import build_model

    from bench import warmup

    mix = common.load_traffic("offline")
    counts = {}
    for name in ("granite-8b-d8", "granite-moe-1b-a400m"):
        config = common.load_config(name)
        eng = SimpleNamespace(
            cb=SimpleNamespace(prefill_chunk_tokens=config["engine"]["prefill_chunk_tokens"],
                               max_len=config["engine"]["max_len"]),
            block_pool=SimpleNamespace(block_size=config["engine"]["kv_block_size"]),
            model=build_model(run.system_config(
                config, common.reference_module(config["reference"]))))
        counts[name] = len(warmup.prefill_shapes(eng, mix))
    assert counts["granite-8b-d8"] == 68
    assert counts["granite-moe-1b-a400m"] > 100 * counts["granite-8b-d8"]
