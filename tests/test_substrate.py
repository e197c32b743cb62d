"""Data pipeline, optimizer, checkpoint, schedules, sharding rules."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.configs import get_smoke_config
from repro.data.synthetic import DataConfig, batch_iterator, make_batch
from repro.optim.adamw import (
    AdamWConfig, adamw_update, clip_by_global_norm, global_norm, init_opt_state,
)
from repro.optim.schedule import cosine_with_warmup


# ----------------------------- data -----------------------------------------


def test_data_deterministic_and_shifted():
    cfg = get_smoke_config("granite_8b")
    b1 = make_batch(cfg, batch=4, seq_len=32, step=7)
    b2 = make_batch(cfg, batch=4, seq_len=32, step=7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shift
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    # different steps/shards differ
    b3 = make_batch(cfg, batch=4, seq_len=32, step=8)
    b4 = make_batch(cfg, batch=4, seq_len=32, step=7, shard=1)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert not np.array_equal(b1["tokens"], b4["tokens"])


def test_data_learnable_structure():
    """The Markov stream must be more predictable than uniform."""
    cfg = get_smoke_config("granite_8b")
    b = make_batch(cfg, batch=8, seq_len=256, step=0, data_cfg=DataConfig(noise=0.05))
    t = np.asarray(b["tokens"])
    # order-2 Markov determinism: the same (prev2, prev1) context almost
    # always yields the same next token (up to the 5% noise hops)
    ctx = {}
    total = hits = 0
    for row in t:
        for i in range(2, len(row)):
            key = (row[i - 2], row[i - 1])
            if key in ctx:
                total += 1
                hits += ctx[key] == row[i]
            else:
                ctx[key] = row[i]
    assert total > 100 and hits / total > 0.75, (hits, total)


def test_iterator_families():
    for arch in ("qwen2_vl_7b", "seamless_m4t_large_v2"):
        cfg = get_smoke_config(arch)
        it = batch_iterator(cfg, batch=2, seq_len=16)
        b = next(it)
        if cfg.family == "vlm":
            assert "patch_embeds" in b
        if cfg.family == "encdec":
            assert "src_embeds" in b


# ----------------------------- optimizer ------------------------------------


def test_adamw_converges_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = init_opt_state(params)
    target = jnp.asarray([1.0, 2.0])
    cfg = AdamWConfig(weight_decay=0.0)
    for i in range(200):
        g = {"w": 2 * (params["w"] - target)}
        params, opt = adamw_update(
            g, opt, params, lr=jnp.asarray(0.1), cfg=cfg, step=jnp.asarray(i + 1)
        )
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target), atol=1e-2)


def test_clip_by_global_norm():
    tree = {"a": jnp.ones(4) * 10.0}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_weight_decay_skips_vectors():
    params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    opt = init_opt_state(params)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    new, _ = adamw_update(zero_g, opt, params, lr=jnp.asarray(0.1),
                          cfg=AdamWConfig(weight_decay=0.1), step=jnp.asarray(1))
    assert float(jnp.max(jnp.abs(new["b"] - 1.0))) < 1e-6  # no decay on bias
    assert float(jnp.max(new["w"])) < 1.0  # decay on matrix


def test_schedule_shape():
    s = jnp.asarray([0, 50, 100, 5000, 10000])
    lr = cosine_with_warmup(s, peak_lr=1e-3, warmup=100, total=10000)
    assert float(lr[0]) == 0.0
    assert float(lr[2]) == pytest.approx(1e-3)
    assert float(lr[4]) < float(lr[2])


# ----------------------------- checkpoint -----------------------------------


def test_checkpoint_roundtrip_and_rotation():
    state = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "step": jnp.asarray(3, jnp.int32),
    }
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3):
            checkpointer.save(d, s, state)
        checkpointer.rotate(d, keep=2)
        assert checkpointer.latest_step(d) == 3
        assert len([x for x in os.listdir(d) if x.startswith("step_")]) == 2
        template = jax.tree.map(np.zeros_like, state)
        restored, step = checkpointer.restore(d, template)
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
        )


def test_checkpoint_atomic_no_partial():
    """A .tmp dir (crashed writer) is never picked up as latest."""
    state = {"w": jnp.ones(3)}
    with tempfile.TemporaryDirectory() as d:
        checkpointer.save(d, 1, state)
        os.makedirs(os.path.join(d, "step_00000002.tmp"))
        assert checkpointer.latest_step(d) == 1


# ----------------------------- sharding rules --------------------------------


def test_logical_rules_divisibility_and_single_use():
    from jax.sharding import PartitionSpec as P
    import numpy as np
    from jax.sharding import Mesh
    from repro.distributed.sharding import DEFAULT_RULES, logical_to_pspec

    del Mesh, np  # Mesh with repeated device objects is invalid; build an
    # abstract mesh instead.
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((4, 4), ("data", "model"))
    # divisible: shard
    assert logical_to_pspec(("vocab",), (512,), DEFAULT_RULES, mesh) == P("model")
    # not divisible: auto-drop
    assert logical_to_pspec(("vocab",), (510,), DEFAULT_RULES, mesh) == P(None)
    # single-use: expert takes model first, mlp drops it
    spec = logical_to_pspec(("expert", "embed", "mlp"), (8, 64, 64), DEFAULT_RULES, mesh)
    assert spec == P("model", "data", None)
