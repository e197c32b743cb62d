"""The repro.ops dispatch layer: backend parity, spec hashability,
capability validation, registration, and platform interpret defaults.

The parity suite is parametrized over *whatever the registry holds*: a
newly registered softmax/attention backend is automatically held to the
exact-softmax oracle within its spec's fixed-point tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs import get_smoke_config
from repro.core.fixedpoint import FORMAT_COLA, FORMAT_MRPC
from repro.core.star_softmax import exact_softmax

RNG = np.random.default_rng(11)


def _logits(shape=(6, 96), scale=4.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, jnp.float32)


def _qkv(b=2, tq=17, tk=40, hq=4, hkv=2, d=32):
    q = jnp.asarray(RNG.normal(size=(b, tq, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, tk, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, tk, hkv, d)), jnp.float32)
    return q, k, v


def _supported(impl, **fields):
    """Build a spec for ``impl``, skipping combos its capabilities reject."""
    spec = ops.SoftmaxSpec(impl=impl, **fields)
    try:
        return ops.validate(spec)
    except ops.CapabilityError:
        backend = ops.get("softmax", impl)
        kinds = backend.capabilities.get("kind")
        if kinds and spec.kind not in kinds:
            return ops.validate(dataclasses.replace(spec, kind=kinds[0]))
        raise


SOFTMAX_IMPLS = [b.impl for b in ops.backends("softmax")]
ATTENTION_IMPLS = [b.impl for b in ops.backends("attention")]


# ---------------------------------------------------------------------------
# parity: every registered backend vs the exact_softmax oracle


@pytest.mark.parametrize("impl", SOFTMAX_IMPLS)
@pytest.mark.parametrize(
    "fmt", [None, FORMAT_MRPC, FORMAT_COLA], ids=["default", "mrpc", "cola"]
)
def test_softmax_backend_parity_vs_oracle(impl, fmt):
    x = _logits()
    fields = {} if fmt is None else {"precision": fmt}
    spec = _supported(impl, **fields)
    out = ops.softmax(x, spec)
    err = float(jnp.max(jnp.abs(out - exact_softmax(x))))
    assert err <= spec.tolerance(), (spec, err)
    np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-5)


@pytest.mark.parametrize("impl", SOFTMAX_IMPLS)
@pytest.mark.parametrize("mode", ["gather", "onehot", "histogram"])
def test_softmax_backend_modes_agree(impl, mode):
    x = _logits()
    spec = _supported(impl, mode=mode)
    base = _supported(impl)
    np.testing.assert_allclose(
        np.asarray(ops.softmax(x, spec)),
        np.asarray(ops.softmax(x, base)),
        atol=2e-6,
    )


@pytest.mark.parametrize("impl", ATTENTION_IMPLS)
def test_attention_backend_parity_star(impl):
    """Every backend implements the same STAR contract: bit-comparable to
    the reference whole-operand engine (DESIGN.md §2/§3)."""
    q, k, v = _qkv()
    spec = ops.AttentionSpec(
        impl=impl, causal=True, block_q=16, block_k=16, block_kv=16
    )
    ref = ops.attention(q, k, v, spec, impl="reference")
    out = ops.attention(q, k, v, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("impl", ATTENTION_IMPLS)
def test_attention_backend_parity_exact_oracle(impl):
    q, k, v = _qkv()
    spec = ops.AttentionSpec(
        impl=impl,
        softmax=ops.SoftmaxSpec(kind="exact"),
        causal=True,
        block_q=16,
        block_k=16,
        block_kv=16,
    )
    ref = ops.attention(q, k, v, spec, impl="reference")
    out = ops.attention(q, k, v, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_matmul_hwmodel_tracks_xla():
    x = jnp.asarray(RNG.normal(size=(32, 128)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(128, 64)) * 0.05, jnp.float32)
    exact = ops.matmul(x, w)
    hw = ops.matmul(x, w, impl="hwmodel")
    rel = float(jnp.max(jnp.abs(hw - exact)) / jnp.max(jnp.abs(exact)))
    assert rel < 0.15, rel  # 8-bit operands + 5-bit ADC quantization


def test_ssd_scan_backends_agree():
    xdt = jnp.asarray(RNG.normal(size=(1, 64, 4, 16)), jnp.float32)
    a = -jnp.abs(jnp.asarray(RNG.normal(size=(1, 64, 4)) * 0.1, jnp.float32))
    bm = jnp.asarray(RNG.normal(size=(1, 64, 16)) * 0.3, jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(1, 64, 16)) * 0.3, jnp.float32)
    y_p, h_p = ops.ssd_scan(xdt, a, bm, cm, chunk=16)
    y_r, h_r = ops.ssd_scan(xdt, a, bm, cm, impl="reference", chunk=16)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r), atol=1e-5)


# ---------------------------------------------------------------------------
# specs: hashable, frozen, jit-cache-stable


def test_specs_hashable_and_value_equal():
    a = ops.AttentionSpec(causal=True, softmax=ops.SoftmaxSpec(precision="auto:mrpc"))
    b = ops.AttentionSpec(causal=True, softmax=ops.SoftmaxSpec(precision="auto:mrpc"))
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.causal = False


def test_spec_as_jit_cache_key_no_retrace():
    import functools

    traces = []

    @functools.partial(jax.jit, static_argnames=("spec",))
    def g(x, spec):
        traces.append(spec)
        return ops.softmax(x, spec)

    x = _logits()
    g(x, spec=ops.SoftmaxSpec())
    g(x + 1, spec=ops.SoftmaxSpec())  # equal spec -> cached, no retrace
    assert len(traces) == 1
    g(x, spec=ops.SoftmaxSpec(precision="auto:mrpc"))  # new spec -> one more
    assert len(traces) == 2


def test_named_precision_policy_resolves():
    assert ops.SoftmaxSpec(precision="auto:mrpc").fmt == FORMAT_MRPC
    assert ops.SoftmaxSpec(kind="exact").fmt is None
    with pytest.raises(ValueError, match="auto:<dataset>"):
        ops.SoftmaxSpec(precision="mrpc")


def test_spec_json_roundtrips():
    import json

    spec = ops.validate(ops.AttentionSpec(impl="pallas", causal=True))
    blob = json.dumps(ops.spec_json(spec))
    assert json.loads(blob)["softmax"]["kind"] == "star"


# ---------------------------------------------------------------------------
# capability validation + registration + use()


def test_capability_mismatch_is_actionable():
    with pytest.raises(ops.CapabilityError) as ei:
        ops.softmax(_logits(), impl="xla", kind="star")
    msg = str(ei.value)
    assert "xla" in msg and "kind" in msg and "reference" in msg  # the fix is named


def test_unknown_backend_lists_registered():
    with pytest.raises(ops.UnknownBackendError, match="pallas"):
        ops.softmax(_logits(), impl="definitely-not-registered")


def test_attention_pv_int8_capability():
    q, k, v = _qkv()
    with pytest.raises(ops.CapabilityError, match="pallas"):
        ops.attention(q, k, v, impl="reference", pv_int8=True)


def test_register_and_use_override():
    def zeros_backend(spec, x, *, where=None, axis=-1):
        return jnp.zeros_like(x)

    ops.register("softmax", "test-zeros", zeros_backend, description="test stub")
    try:
        x = _logits()
        # explicit impl routes to the new backend
        assert float(jnp.max(ops.softmax(x, impl="test-zeros"))) == 0.0
        # use() retargets dispatches that asked for another impl
        with ops.use(softmax="test-zeros"):
            assert float(jnp.max(ops.softmax(x, impl="reference"))) == 0.0
        # and the override frame pops
        assert float(jnp.max(ops.softmax(x, impl="reference"))) > 0.0
    finally:
        ops.unregister("softmax", "test-zeros")
    with pytest.raises(ops.UnknownBackendError):
        ops.softmax(x, impl="test-zeros")


def test_use_rejects_unknown_keys():
    with pytest.raises(ops.OpDispatchError, match="valid keys"):
        with ops.use(softmaxx="reference"):
            pass


def test_duplicate_registration_requires_overwrite():
    def stub(spec, x, *, where=None, axis=-1):
        return x

    ops.register("softmax", "test-dup", stub)
    try:
        with pytest.raises(ops.OpDispatchError, match="overwrite"):
            ops.register("softmax", "test-dup", stub)
        ops.register("softmax", "test-dup", stub, overwrite=True)
    finally:
        ops.unregister("softmax", "test-dup")


# ---------------------------------------------------------------------------
# platform + config integration


def test_default_interpret_matches_platform(monkeypatch):
    assert ops.default_interpret() == (ops.detected_platform() != "tpu")
    monkeypatch.setenv("REPRO_OPS_INTERPRET", "0")
    assert ops.default_interpret() is False
    monkeypatch.setenv("REPRO_OPS_INTERPRET", "1")
    assert ops.default_interpret() is True


def test_resolved_spec_has_concrete_interpret():
    spec = ops.validate(ops.SoftmaxSpec(impl="pallas"))
    assert spec.interpret in (True, False)


def test_config_carries_specs():
    cfg = get_smoke_config("granite_8b")
    spec = cfg.attention_spec
    assert spec.impl == "xla" and spec.block_kv == 32
    assert cfg.softmax_spec.kind == "star"
    # the test idiom: legacy loose-field replace still wins over the spec
    exact = dataclasses.replace(cfg, softmax_kind="exact")
    assert exact.softmax_spec.kind == "exact"
    assert exact.attention_spec.softmax.kind == "exact"


def test_config_validates_through_registry():
    cfg = get_smoke_config("granite_8b")
    ops.validate(cfg.attention_spec)
    ops.validate(cfg.softmax_spec)


def test_config_legacy_block_size_replace_wins():
    cfg = get_smoke_config("granite_8b")  # carries block_kv=32 in its spec
    bumped = dataclasses.replace(cfg, attn_block_size=64)
    spec = bumped.attention_spec
    assert spec.block_kv == 64 and spec.block_q == 64 and spec.block_k == 64


def test_moe_router_exact_falls_back_from_star_only_impl():
    # a star-only softmax impl + an exact-kind override must not raise at
    # the MoE router (layers.moe reroutes the oracle through reference)
    from repro.models.layers import moe, spec_moe
    from repro.models.param import materialize

    cfg = dataclasses.replace(
        get_smoke_config("granite_moe_1b_a400m"),
        softmax=ops.SoftmaxSpec(impl="pallas", kind="star"),
        softmax_kind="exact",  # the legacy-replace idiom
    )
    params = materialize(spec_moe(cfg), jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.normal(size=(2, 8, cfg.d_model)), jnp.float32)
    out = moe(params, x, cfg)
    assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# paged attention (block-pool KV decode — DESIGN.md §8)

PAGED_IMPLS = [b.impl for b in ops.backends("paged_attention")]


def _paged_operands(s=3, w=3, bs=4, hq=4, hkv=2, d=16):
    n = s * w + 1  # block 0 reserved as scratch
    q = jnp.asarray(RNG.normal(size=(s, 1, hq, d)), jnp.float32)
    # a stack of one layer's pools: [L=1, N, bs, Hkv, D]
    kp = jnp.asarray(RNG.normal(size=(1, n, bs, hkv, d)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(1, n, bs, hkv, d)), jnp.float32)
    tables = jnp.asarray(
        [[i * w + j + 1 for j in range(w)] for i in range(s)], jnp.int32
    )
    kvl = jnp.asarray([6, 11, 2], jnp.int32)
    return q, kp, vp, tables, kvl


def test_paged_attention_registered_backends():
    assert {"reference", "xla", "pallas"} <= set(PAGED_IMPLS)
    assert ops.get("attention", "paged") is not None  # the layout marker


@pytest.mark.parametrize("impl", PAGED_IMPLS)
def test_paged_attention_backend_parity(impl):
    q, kp, vp, tables, kvl = _paged_operands()
    spec = ops.PagedAttentionSpec(impl=impl, block_size=4)
    ref = ops.paged_attention(
        q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0, kv_len=10, impl="reference"
    )
    out = ops.paged_attention(
        q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0, kv_len=10
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("impl", PAGED_IMPLS)
def test_paged_attention_matches_dense_gather(impl):
    """Gathering a block table reproduces the dense cache: the paged op
    must agree with dense attention over the manually flattened blocks."""
    q, kp, vp, tables, kvl = _paged_operands()
    s, w = tables.shape
    bs = kp.shape[2]
    flat = np.asarray(tables).reshape(-1)
    kd = jnp.asarray(np.asarray(kp[0])[flat].reshape(s, w * bs, *kp.shape[3:])[:, :10])
    vd = jnp.asarray(np.asarray(vp[0])[flat].reshape(s, w * bs, *vp.shape[3:])[:, :10])
    dense = ops.attention(
        q,
        kd,
        vd,
        ops.AttentionSpec(impl="reference", causal=False),
        kv_valid_len=kvl,
    )
    out = ops.paged_attention(
        q,
        kp,
        vp,
        tables,
        ops.PagedAttentionSpec(impl=impl, block_size=4),
        kv_valid_len=kvl,
        layer=0,
        kv_len=10,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=3e-6)


def test_paged_attention_use_override():
    q, kp, vp, tables, kvl = _paged_operands()
    ref = ops.paged_attention(
        q, kp, vp, tables, kv_valid_len=kvl, layer=0, kv_len=10, impl="reference"
    )
    with ops.use(paged_attention="reference"):
        out = ops.paged_attention(
            q,
            kp,
            vp,
            tables,
            ops.PagedAttentionSpec(impl="xla"),
            kv_valid_len=kvl,
        layer=0,
            kv_len=10,
        )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_attention_pallas_capability():
    q, kp, vp, tables, kvl = _paged_operands()
    spec = ops.PagedAttentionSpec(
        impl="pallas", softmax=ops.SoftmaxSpec(kind="star_ste")
    )
    with pytest.raises(ops.CapabilityError, match="pallas"):
        ops.paged_attention(q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0)


def test_paged_spec_validation_and_json():
    import json

    with pytest.raises(ValueError, match="block_size"):
        ops.PagedAttentionSpec(block_size=0)
    spec = ops.validate(ops.PagedAttentionSpec(impl="pallas"))
    assert spec.interpret in (True, False)
    blob = json.dumps(ops.spec_json(spec))
    assert json.loads(blob)["op"] == "paged_attention"


def test_config_derives_paged_spec():
    cfg = get_smoke_config("granite_8b")
    spec = cfg.paged_attention_spec
    assert spec.impl == "xla"
    assert spec.softmax == cfg.softmax_spec
    # the "paged" marker impl maps to xla math for the inner op
    paged_cfg = dataclasses.replace(cfg, attn_impl="paged")
    assert paged_cfg.attention_spec.impl == "paged"
    assert paged_cfg.paged_attention_spec.impl == "xla"
    ops.validate(paged_cfg.attention_spec)  # the marker impl is registered
