"""Quantized paged KV cache (DESIGN.md §13).

The claims pinned here:

1. **Roundtrip bound** — encode/decode error is ≤ ``scale / 2`` per
   element for int8 (the rounding grid) and ≤ ``16 * scale`` for
   fp8_e4m3 (half the widest e4m3 ulp), property-swept over magnitudes.
2. **Kernel = oracle** — ``pallas_paged`` with in-kernel dequant matches
   the gather backends (which dequantize the gathered codes — the exact
   same ``codes * scale`` expression) to float32 roundoff, NOT to a loose
   quantization tolerance: both paths read identical operands.
3. **Gather-freedom survives quantization** — the quantized kernel's
   jaxpr still contains no ``[S, W*bs, Hkv, D]`` operand at any
   precision; scales ride scalar prefetch.
4. **Dispatch guardrails** — ``kv_scales`` is required iff the spec says
   quantized; the guard's fallback strips ``kv_dtype`` like it strips
   faults.
5. **Engine parity** — int8 serving through the kernel is token-identical
   to int8 serving through the gather oracle (dense, ring-wrap, M-RoPE
   archs); fp32 paged serving is untouched; int8 bytes/token ≤ 0.55x
   fp32 (the CI compression gate's in-repo twin).
6. **Deprecation sweep** — no in-repo caller imports the retired
   ``kernels/*/ops.py`` shims (``tests/test_kernel_shims.py`` pins the
   shims themselves and is the one allowed importer).
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs import get_smoke_config
from repro.core import kvquant
from repro.models.param import materialize
from repro.models.registry import build_model
from repro.ops.guard import clean_spec
from repro.serve import paged as serve_paged
from repro.serve.engine import (
    ContinuousBatchingEngine,
    ContinuousConfig,
)

KEY = jax.random.PRNGKey(0)
RNG = np.random.default_rng(23)
MAX_LEN = 40
QUANT_DTYPES = ("int8", "fp8_e4m3")


# ---------------------------------------------------------------------------
# core.kvquant: roundtrip property + dtype plumbing


@pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
@pytest.mark.parametrize("magnitude", [1e-3, 1.0, 30.0])
def test_roundtrip_error_bound(kv_dtype, magnitude):
    """Per-element |decode(encode(x)) - x| stays inside the grid bound."""
    x = jnp.asarray(RNG.normal(size=(4, 16, 2, 32)) * magnitude, jnp.float32)
    codes, scale = kvquant.quantize_blocks(x, kv_dtype)
    assert codes.dtype == kvquant.storage_dtype(kv_dtype)
    assert scale.shape == (4, 2) and scale.dtype == jnp.float32
    back = kvquant.decode(codes, scale[:, None, :, None])
    err = np.asarray(jnp.abs(back - x))
    # int8: round-to-nearest on a uniform grid -> half a step.  fp8_e4m3:
    # scaling maps absmax to 448, so the widest ulp in play is 32 -> 16.
    bound = 0.5 if kv_dtype == "int8" else 16.0
    # * (1 + 1e-5): the decode multiply itself rounds in float32, which can
    # push an exactly-half-ulp case a few f32 ulps past the analytic bound
    limit = bound * np.asarray(scale)[:, None, :, None] * (1 + 1e-5) + 1e-12
    assert np.all(err <= limit)


def test_zero_block_roundtrips_to_exact_zero():
    x = jnp.zeros((2, 8, 2, 16), jnp.float32)
    for kv_dtype in QUANT_DTYPES:
        codes, scale = kvquant.quantize_blocks(x, kv_dtype)
        back = np.asarray(kvquant.decode(codes, scale[:, None, :, None]))
        assert np.all(back == 0.0) and np.all(np.isfinite(back))


def test_fp8_overflow_clips_instead_of_nan():
    """Values past an undersized scale's range must clip, never NaN — the
    stale-stamp decode path writes rows bigger than the stamped absmax."""
    stale_scale = jnp.float32(0.01)
    codes = kvquant.encode(jnp.asarray([1e4, -1e4]), stale_scale, "fp8_e4m3")
    back = np.asarray(kvquant.decode(codes, stale_scale))
    assert np.all(np.isfinite(back))
    assert back[0] == pytest.approx(448 * 0.01) and back[1] == -back[0]


def test_dtype_mapping_roundtrip():
    for kv_dtype in QUANT_DTYPES:
        assert kvquant.dtype_of(kvquant.storage_dtype(kv_dtype)) == kv_dtype
    assert kvquant.dtype_of(jnp.float32) == "fp32"
    assert kvquant.dtype_of(jnp.bfloat16) == "fp32"
    with pytest.raises(ValueError, match="fp32"):
        kvquant.storage_dtype("fp32")
    with pytest.raises(ValueError, match="kv_dtype"):
        kvquant.validate_kv_dtype("int4")


def test_spec_and_pool_validate_kv_dtype():
    with pytest.raises(ValueError, match="kv_dtype"):
        ops.PagedAttentionSpec(kv_dtype="int4")
    with pytest.raises(ValueError, match="kv_dtype"):
        serve_paged.BlockPool(4, 4, kv_dtype="int4")
    # the allocator's jax-free mirror of the dtype list must not drift
    assert serve_paged.KV_DTYPES == kvquant.KV_DTYPES


def test_guard_clean_spec_strips_quantization_and_faults():
    fault = ops.FaultModel(stuck_on_rate=0.01, seed=0)
    sm = clean_spec(ops.SoftmaxSpec(impl="pallas", fault=fault), "reference")
    assert sm.impl == "reference" and sm.fault is None
    pa = clean_spec(ops.PagedAttentionSpec(kv_dtype="int8"), "xla")
    assert pa.impl == "xla" and pa.kv_dtype == "fp32"


# ---------------------------------------------------------------------------
# op level: kernel vs dequant oracle, guardrails, gather-freedom


def _quantized_operands(kv_dtype, s=3, w=4, bs=8, hq=4, hkv=2, d=16,
                        lens=(6, 25, 11)):
    n = s * w + 1
    q = jnp.asarray(RNG.normal(size=(s, 1, hq, d)), jnp.float32)
    kf = jnp.asarray(RNG.normal(size=(n, bs, hkv, d)), jnp.float32)
    vf = jnp.asarray(RNG.normal(size=(n, bs, hkv, d)), jnp.float32)
    kp, ks = kvquant.quantize_blocks(kf, kv_dtype)
    vp, vs = kvquant.quantize_blocks(vf, kv_dtype)
    perm = RNG.permutation(np.arange(1, n))
    tables = jnp.asarray(perm[: s * w].reshape(s, w), jnp.int32)
    kvl = jnp.asarray(lens, jnp.int32)
    # a stack of one layer's pools: [L=1, N, ...]
    return q, kp[None], vp[None], (ks[None], vs[None]), tables, kvl


@pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
@pytest.mark.parametrize("kind", ["star", "exact"])
def test_kernel_parity_vs_dequant_oracle(kv_dtype, kind):
    """Float32-roundoff parity: both paths evaluate codes * scale."""
    q, kp, vp, scales, tables, kvl = _quantized_operands(kv_dtype)
    def mk(impl):
        return ops.PagedAttentionSpec(
            impl=impl, block_size=8, kv_dtype=kv_dtype,
            softmax=ops.SoftmaxSpec(kind=kind),
        )
    ref = ops.paged_attention(q, kp, vp, tables, mk("xla"),
                              kv_valid_len=kvl, layer=0, kv_scales=scales)
    out = ops.paged_attention(q, kp, vp, tables, mk("pallas_paged"),
                              kv_valid_len=kvl, layer=0, kv_scales=scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_kernel_parity_ring_clamp_quantized():
    q, kp, vp, scales, tables, kvl = _quantized_operands(
        "int8", lens=(30, 32, 12))
    def mk(impl):
        return ops.PagedAttentionSpec(impl=impl, block_size=8,
                                      kv_dtype="int8")
    ref = ops.paged_attention(q, kp, vp, tables, mk("reference"),
                              kv_valid_len=kvl, layer=0, kv_len=16, kv_scales=scales)
    out = ops.paged_attention(q, kp, vp, tables, mk("pallas_paged"),
                              kv_valid_len=kvl, layer=0, kv_len=16, kv_scales=scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_int8_output_close_to_fp32_reference():
    """The accuracy claim itself, pinned: quantizing KV moves the attention
    output by a bounded amount, it does not change its shape/scale."""
    q, kp, vp, scales, tables, kvl = _quantized_operands("int8")
    spec8 = ops.PagedAttentionSpec(impl="xla", block_size=8, kv_dtype="int8")
    out8 = ops.paged_attention(q, kp, vp, tables, spec8,
                               kv_valid_len=kvl, layer=0, kv_scales=scales)
    kf = kvquant.decode(kp, scales[0][..., None, :, None])
    vf = kvquant.decode(vp, scales[1][..., None, :, None])
    spec32 = ops.PagedAttentionSpec(impl="xla", block_size=8)
    out32 = ops.paged_attention(q, kf, vf, tables, spec32, kv_valid_len=kvl, layer=0)
    # identical codes: dequantized-operand attention == quantized attention
    np.testing.assert_allclose(np.asarray(out8), np.asarray(out32), atol=3e-6)


def test_dispatch_requires_scales_iff_quantized():
    q, kp, vp, scales, tables, kvl = _quantized_operands("int8")
    spec = ops.PagedAttentionSpec(impl="xla", block_size=8, kv_dtype="int8")
    with pytest.raises(ops.OpDispatchError, match="kv_scales"):
        ops.paged_attention(q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0)
    fp32 = ops.PagedAttentionSpec(impl="xla", block_size=8)
    with pytest.raises(ops.OpDispatchError, match="kv_scales"):
        ops.paged_attention(
            q, kp.astype(jnp.float32), vp.astype(jnp.float32), tables, fp32,
            kv_valid_len=kvl, layer=0, kv_scales=scales,
        )


@pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
def test_quantized_kernel_never_materializes_gathered_window(kv_dtype):
    """No [S, W*bs, Hkv, D] operand at ANY dtype: the dequantized window
    must not exist either — scales ride scalar prefetch, dequant happens
    one page at a time in VMEM."""
    q, kp, vp, scales, tables, kvl = _quantized_operands(kv_dtype)
    s, w = tables.shape
    _, _, bs, hkv, d = kp.shape
    spec = ops.PagedAttentionSpec(
        impl="pallas_paged", block_size=bs, kv_dtype=kv_dtype)

    def call(q, kp, vp, ks, vs, tables, kvl):
        return ops.paged_attention(q, kp, vp, tables, spec,
                                   kv_valid_len=kvl, layer=0, kv_scales=(ks, vs))

    assert not ops.materializes(
        call, (s, w * bs, hkv, d), q, kp, vp, *scales, tables, kvl)


def test_counted_bytes_int8_meets_compression_target():
    """The kernel_bench acceptance shape in-repo: counted int8 bytes/token
    (codes + scale rows) ≤ 0.55x the fp32 bytes/token at pool-256/live-8."""
    common = dict(impl="pallas_paged", table_width=16, block_size=16,
                  live_lens=[8] * 8, num_kv_heads=2, head_dim=64)
    fp32 = ops.paged_gather_bytes(dtype_bytes=4, **common)
    int8 = ops.paged_gather_bytes(
        dtype_bytes=1, scale_bytes_per_block=8 * 2, **common)
    assert int8 / fp32 <= 0.55


# ---------------------------------------------------------------------------
# model/cache layer: write-path quantization + scale lifecycle


def test_paged_cache_leaves_and_write_roundtrip():
    cfg = get_smoke_config("granite_8b")
    model = build_model(cfg)
    pool = model.init_paged_cache(9, 4, 2, kv_dtype="int8")
    assert pool["layers"]["k"].dtype == jnp.int8
    assert pool["layers"]["k_scale"].shape == (
        cfg.num_layers, 9, cfg.num_kv_heads)
    # fp32 pools carry no scale leaves at all — the layout marker
    assert "k_scale" not in model.init_paged_cache(9, 4, 2)["layers"]

    params = materialize(model.param_specs(), KEY)
    # max_len 8 -> an 8-row prefill cache, exactly the 2 blocks the table holds
    _, cache = model.prefill(
        params, jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 7)), jnp.int32),
        8)
    pool = model.write_slot_paged(pool, cache, 0, jnp.asarray([1, 2], jnp.int32))
    k = np.asarray(cache["layers"]["k"])[:, 0, :7]
    got = kvquant.decode(
        pool["layers"]["k"][:, [1, 2]],
        pool["layers"]["k_scale"][:, [1, 2]][:, :, None, :, None],
    )
    got = np.asarray(got).reshape(k.shape[0], 8, *k.shape[2:])[:, :7]
    scale = np.asarray(pool["layers"]["k_scale"][:, [1, 2]])
    assert np.max(np.abs(got - k)) <= 0.5 * scale.max() + 1e-12


def test_copy_block_moves_scale_rows():
    cfg = get_smoke_config("granite_8b")
    model = build_model(cfg)
    pool = model.init_paged_cache(5, 4, 2, kv_dtype="int8")
    layers = dict(pool["layers"])
    layers["k_scale"] = layers["k_scale"].at[:, 2].set(7.0)
    layers["v_scale"] = layers["v_scale"].at[:, 2].set(3.0)
    pool = {**pool, "layers": layers}
    pool = model.copy_block(pool, jnp.int32(2), jnp.int32(4))
    assert np.all(np.asarray(pool["layers"]["k_scale"][:, 4]) == 7.0)
    assert np.all(np.asarray(pool["layers"]["v_scale"][:, 4]) == 3.0)


# ---------------------------------------------------------------------------
# engine: greedy token parity at int8, fp32 untouched, byte accounting


def _model_params(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    return cfg, materialize(model.param_specs(), KEY)


def _serve(cfg, params, prompts, gens, kv_dtype, impl, frontends=None,
           **cb_kw):
    cb = ContinuousConfig(num_slots=2, max_len=MAX_LEN, kv_layout="paged",
                          kv_block_size=4, kv_dtype=kv_dtype, **cb_kw)
    fes = frontends or [{} for _ in prompts]
    with ops.use(paged_attention=impl):
        eng = ContinuousBatchingEngine(cfg, params, cb)
        uids = [eng.submit(p, g, **fe)
                for p, g, fe in zip(prompts, gens, fes)]
        done = eng.run()
    return [done[u] for u in uids], eng


@pytest.mark.parametrize("arch,lens", [
    ("granite_8b", (5, 11, 8, 3)),       # dense append path
    ("mixtral_8x22b", (20, 11, 18, 3)),  # window=16 ring: stamps must
                                         # survive wrap-around laps
])
def test_engine_int8_kernel_matches_int8_oracle(arch, lens):
    cfg, params = _model_params(arch)
    prompts = [RNG.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    gens = [4, 2, 5, 3]
    got, eng = _serve(cfg, params, prompts, gens, "int8", "pallas_paged")
    want, _ = _serve(cfg, params, prompts, gens, "int8", "xla")
    assert got == want
    st = eng.kv_stats()
    assert st["kv_dtype"] == "int8"


def test_engine_int8_vlm_mrope_parity():
    cfg, params = _model_params("qwen2_vl_7b")
    prompts = [RNG.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9)]
    pe = [{"patch_embeds": RNG.standard_normal(
        (1, cfg.num_patches, cfg.frontend_dim)).astype(np.float32)}
        for _ in prompts]
    got, _ = _serve(cfg, params, prompts, [3, 2], "int8", "pallas_paged", pe)
    want, _ = _serve(cfg, params, prompts, [3, 2], "int8", "xla", pe)
    assert got == want


def test_engine_int8_prefix_cache_parity():
    """Shared prefix blocks carry their scales: adoption + CoW discipline
    must keep kernel and oracle token-identical."""
    cfg, params = _model_params("granite_8b")
    prefix = RNG.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    suffix = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [prefix, np.concatenate([prefix, suffix])]

    def serve_sequential(impl):
        # two phases so the first prompt's blocks are in the trie before
        # the second prompt prefills — that second prefill must adopt the
        # shared (quantized) prefix blocks
        cb = ContinuousConfig(num_slots=2, max_len=MAX_LEN,
                              kv_layout="paged", kv_block_size=4,
                              kv_dtype="int8", prefix_cache=True,
                              prefill_chunk_tokens=8)
        with ops.use(paged_attention=impl):
            eng = ContinuousBatchingEngine(cfg, params, cb)
            u0 = eng.submit(prompts[0], 3)
            first = eng.run()[u0]
            u1 = eng.submit(prompts[1], 3)
            second = eng.run()[u1]
        return [first, second], eng

    got, eng = serve_sequential("pallas_paged")
    want, _ = serve_sequential("xla")
    assert got == want
    assert eng.kv_stats()["prefix"]["hits"] == 1


def test_engine_fp32_unaffected_and_int8_compresses():
    """fp32 serving is byte-identical to before this feature (no scale
    leaves, same tokens as the oracle) and the engine-counted bytes/token
    hits the ≤ 0.55x acceptance ratio."""
    cfg, params = _model_params("granite_8b")
    prompts = [RNG.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 8)]
    got, e32 = _serve(cfg, params, prompts, [3, 3], "fp32", "pallas_paged")
    want, _ = _serve(cfg, params, prompts, [3, 3], "fp32", "xla")
    assert got == want
    assert "k_scale" not in e32.pool["layers"]
    _, e8 = _serve(cfg, params, prompts, [3, 3], "int8", "pallas_paged")
    b32 = e32.kv_stats()["kv_bytes_per_token"]
    b8 = e8.kv_stats()["kv_bytes_per_token"]
    assert b8 <= 0.55 * b32
    # row bytes derive from the actual leaf dtypes (satellite: kv_row_bytes)
    assert e8.kv_row_bytes() * 4 == e32.kv_row_bytes()


def test_engine_rejects_quantized_dense_layout():
    cfg, params = _model_params("granite_8b")
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingEngine(
            cfg, params,
            ContinuousConfig(num_slots=2, max_len=MAX_LEN,
                             kv_layout="dense", kv_dtype="int8"))


# ---------------------------------------------------------------------------
# deprecation sweep: the kernels/*/ops.py shims have no in-repo importers


def test_no_in_repo_shim_importers():
    """The shims are retired: only ``tests/test_kernel_shims.py`` (which
    pins the shims' own deprecation behaviour) may import them.  Grep the
    tree so a regressed import fails here, not in review."""
    root = pathlib.Path(__file__).resolve().parents[1]
    pat = re.compile(
        r"repro\.kernels\.(star_softmax|flash_star|crossbar_matmul|ssd_scan)"
        r"\.ops\b")
    allowed = {"tests/test_kernel_shims.py"}
    offenders = []
    for sub in ("src", "tests", "benchmarks"):
        for path in (root / sub).rglob("*.py"):
            rel = path.relative_to(root).as_posix()
            if rel in allowed or path.name == "ops.py":
                continue
            if pat.search(path.read_text()):
                offenders.append(rel)
    assert not offenders, f"retired shim imported by: {offenders}"
