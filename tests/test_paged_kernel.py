"""Gather-free paged-attention decode kernel (DESIGN.md §11).

Three claims pinned here:

1. **Parity** — ``("paged_attention", "pallas_paged")`` matches the gather
   reference backend within spec tolerance across ragged lengths, block
   sizes {8, 16}, STAR and exact softmax, ring (sliding-window) clamping,
   GQA ratios, and through the serve engine (greedy token parity incl.
   M-RoPE and ring-wrap archs).
2. **Gather-freedom** — the kernel's jaxpr contains no gathered
   ``[S, W*bs, Hkv, D]`` operand at any point, while every gather adapter
   provably materializes one (the structural form of the perf claim; the
   counted-traffic form lives in ``ops.paged_gather_bytes``).
3. **Capability envelope** — like the other fused kernels, pallas_paged
   declares no per-cell fault path and no ``star_ste`` kind; dispatch must
   refuse, not silently degrade.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs import get_smoke_config
from repro.kernels.paged_attention import paged_flash_attention
from repro.models.param import materialize
from repro.models.registry import build_model
from repro.serve.engine import (
    ContinuousBatchingEngine,
    ContinuousConfig,
    ServeConfig,
    ServeEngine,
)

KEY = jax.random.PRNGKey(0)
RNG = np.random.default_rng(7)
MAX_LEN = 40


def _operands(s=3, w=4, bs=8, hq=4, hkv=2, d=16, lens=(6, 25, 0)):
    n = s * w + 1  # block 0 reserved as scratch
    q = jnp.asarray(RNG.normal(size=(s, 1, hq, d)), jnp.float32)
    # a stack of one layer's pools: [L=1, N, bs, Hkv, D]
    kp = jnp.asarray(RNG.normal(size=(1, n, bs, hkv, d)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(1, n, bs, hkv, d)), jnp.float32)
    # shuffled non-contiguous tables: the kernel must follow the table,
    # not the pool order
    perm = RNG.permutation(np.arange(1, n))
    tables = jnp.asarray(perm[: s * w].reshape(s, w), jnp.int32)
    kvl = jnp.asarray(lens, jnp.int32)
    return q, kp, vp, tables, kvl


def _spec(impl, kind, bs):
    return ops.PagedAttentionSpec(
        impl=impl, block_size=bs, softmax=ops.SoftmaxSpec(kind=kind)
    )


# ---------------------------------------------------------------------------
# op-level parity vs the gather reference oracle


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("kind", ["star", "exact"])
def test_parity_ragged_vs_gather_reference(bs, kind):
    q, kp, vp, tables, kvl = _operands(bs=bs, lens=(6, 25, 2))
    ref = ops.paged_attention(
        q, kp, vp, tables, _spec("reference", kind, bs), kv_valid_len=kvl, layer=0
    )
    out = ops.paged_attention(
        q, kp, vp, tables, _spec("pallas_paged", kind, bs), kv_valid_len=kvl, layer=0
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("kind", ["star", "exact"])
def test_empty_slot_emits_zeros(kind):
    """valid == 0 (a free serve slot) emits exactly zeros, never NaN —
    the fused-kernel contract (flash_star does the same; the *reference*
    exact path instead averages the masked garbage window, which is why
    the parity sweep never includes a zero-length slot)."""
    q, kp, vp, tables, kvl = _operands(lens=(6, 25, 0))
    out = ops.paged_attention(
        q, kp, vp, tables, _spec("pallas_paged", kind, 8), kv_valid_len=kvl, layer=0
    )
    assert np.all(np.asarray(out)[2] == 0.0)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("lens", [(1, 8, 9), (32, 17, 24)])
def test_parity_block_boundary_lengths(lens):
    """Valid lengths on and just past block edges (the mask/clamp seams)."""
    q, kp, vp, tables, kvl = _operands(bs=8, lens=lens)
    ref = ops.paged_attention(
        q, kp, vp, tables, _spec("reference", "star", 8), kv_valid_len=kvl, layer=0
    )
    out = ops.paged_attention(
        q, kp, vp, tables, _spec("pallas_paged", "star", 8), kv_valid_len=kvl, layer=0
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_parity_ring_clamp_kv_len():
    """Ring caches pass kv_len = cache_t < table capacity: the kernel must
    clamp the ragged lengths exactly like the gather path crops rows."""
    q, kp, vp, tables, kvl = _operands(bs=8, w=4, lens=(30, 32, 12))
    ref = ops.paged_attention(
        q, kp, vp, tables, _spec("reference", "star", 8),
        kv_valid_len=kvl, layer=0, kv_len=16,
    )
    out = ops.paged_attention(
        q, kp, vp, tables, _spec("pallas_paged", "star", 8),
        kv_valid_len=kvl, layer=0, kv_len=16,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
def test_parity_gqa_ratios(hq, hkv):
    q, kp, vp, tables, kvl = _operands(hq=hq, hkv=hkv, lens=(6, 25, 11))
    ref = ops.paged_attention(
        q, kp, vp, tables, _spec("reference", "exact", 8), kv_valid_len=kvl, layer=0
    )
    out = ops.paged_attention(
        q, kp, vp, tables, _spec("pallas_paged", "exact", 8), kv_valid_len=kvl, layer=0
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("impl", ["reference", "pallas_paged"])
@pytest.mark.parametrize("layer", [0, 2])
def test_stacked_pools_read_the_named_layer(impl, layer):
    """``layer`` reads one slab of ``[L, N, bs, Hkv, D]`` pools (the serve
    tick's layout) exactly as the same call on that slab as a stack of
    one; the other
    layers hold different values, so a wrong slab shows."""
    q, kp, vp, tables, kvl = _operands(lens=(6, 25, 11))
    kst = jnp.concatenate([kp, -kp, 2 * kp])
    vst = jnp.concatenate([vp, vp + 1.0, -vp])
    spec = _spec(impl, "star", 8)
    want = ops.paged_attention(q, kst[layer][None], vst[layer][None], tables,
                               spec, kv_valid_len=kvl, layer=0)
    got = ops.paged_attention(q, kst, vst, tables, spec, kv_valid_len=kvl,
                              layer=jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_rejects_bad_gqa_and_multitoken_queries():
    q, kp, vp, tables, kvl = _operands()
    with pytest.raises(AssertionError, match="GQA"):
        paged_flash_attention(
            q[:, 0, :3], kp, vp, tables, kvl, layer=0, fmt=None,
            interpret=True,
        )
    q2 = jnp.concatenate([q, q], axis=1)  # Tq = 2
    with pytest.raises(ops.CapabilityError, match="decode kernel"):
        ops.paged_attention(
            q2, kp, vp, tables, _spec("pallas_paged", "star", 8),
            kv_valid_len=kvl, layer=0,
        )


# ---------------------------------------------------------------------------
# gather-freedom: the structural no-[S, W*bs, H, D] assertion


def _materializes_gathered_operand(impl) -> bool:
    q, kp, vp, tables, kvl = _operands()
    s, w = tables.shape
    _, _, bs, hkv, d = kp.shape
    spec = _spec(impl, "star", bs)

    def call(q, kp, vp, tables, kvl):
        return ops.paged_attention(q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0)

    return ops.materializes(call, (s, w * bs, hkv, d), q, kp, vp, tables, kvl)


def test_pallas_paged_never_materializes_the_gathered_window():
    assert not _materializes_gathered_operand("pallas_paged")


@pytest.mark.parametrize("impl", ["reference", "xla"])
def test_gather_adapters_do_materialize_it(impl):
    """The control: the assertion above is meaningful because the same
    probe finds the dense [S, W*bs, Hkv, D] operand in every gather
    adapter's program."""
    assert _materializes_gathered_operand(impl)


def test_counted_gather_bytes_model():
    common = dict(table_width=8, block_size=16, num_kv_heads=2, head_dim=64)
    xla = ops.paged_gather_bytes("xla", live_lens=[8, 24, 0], **common)
    pp = ops.paged_gather_bytes("pallas_paged", live_lens=[8, 24, 0], **common)
    row = 2 * 2 * 64 * 4  # K+V rows, f32
    assert xla == 3 * 8 * 16 * row  # full table window, occupancy-blind
    # live pages only; the empty slot still touches its one clamped page
    assert pp == (16 + 32 + 16) * row
    assert xla / pp >= 1.5  # the BENCH_paged_decode acceptance shape


# ---------------------------------------------------------------------------
# capability envelope


def test_fault_capability_refused():
    q, kp, vp, tables, kvl = _operands()
    fault = ops.FaultModel(stuck_on_rate=0.01, seed=0)
    spec = ops.PagedAttentionSpec(
        impl="pallas_paged", softmax=ops.SoftmaxSpec(kind="star", fault=fault)
    )
    with pytest.raises(ops.CapabilityError, match="pallas_paged"):
        ops.paged_attention(q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0)


def test_star_ste_kind_refused():
    q, kp, vp, tables, kvl = _operands()
    spec = ops.PagedAttentionSpec(
        impl="pallas_paged", softmax=ops.SoftmaxSpec(kind="star_ste")
    )
    with pytest.raises(ops.CapabilityError, match="pallas_paged"):
        ops.paged_attention(q, kp, vp, tables, spec, kv_valid_len=kvl, layer=0)


# ---------------------------------------------------------------------------
# serve-engine token parity through the gather-free kernel


def _model_params(arch="granite_8b"):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    return cfg, materialize(model.param_specs(), KEY)


def _expected(cfg, params, prompts, gens, frontends=None):
    ref = ServeEngine(cfg, params, ServeConfig(max_len=MAX_LEN, temperature=0.0))
    fes = frontends or [{} for _ in prompts]
    return [
        np.asarray(ref.generate(
            jnp.asarray(p)[None], g,
            **{k: jnp.asarray(v) for k, v in fe.items()})[0])[0].tolist()
        for p, g, fe in zip(prompts, gens, fes)
    ]


@pytest.mark.parametrize("arch,lens", [
    ("granite_8b", (5, 11, 8, 3)),       # dense append path
    ("mixtral_8x22b", (20, 11, 18, 3)),  # window=16 ring: prompts wrap
])
def test_engine_greedy_parity_pallas_paged(arch, lens):
    cfg, params = _model_params(arch)
    prompts = [RNG.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    gens = [4, 2, 5, 3]
    expected = _expected(cfg, params, prompts, gens)
    with ops.use(paged_attention="pallas_paged"):
        eng = ContinuousBatchingEngine(
            cfg, params,
            ContinuousConfig(num_slots=2, max_len=MAX_LEN,
                             kv_layout="paged", kv_block_size=4))
        uids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        done = eng.run()
    assert [done[u] for u in uids] == expected
    # the engine accounted gather-free traffic for the resolved impl
    assert eng.kv_stats()["gather_bytes_per_token"] > 0


def test_engine_vlm_mrope_parity_pallas_paged():
    cfg, params = _model_params("qwen2_vl_7b")
    prompts = [RNG.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9)]
    pe = [RNG.standard_normal((1, cfg.num_patches, cfg.frontend_dim))
          .astype(np.float32) for _ in prompts]
    gens = [3, 2]
    expected = _expected(cfg, params, prompts, gens,
                         [{"patch_embeds": e} for e in pe])
    with ops.use(paged_attention="pallas_paged"):
        eng = ContinuousBatchingEngine(
            cfg, params,
            ContinuousConfig(num_slots=2, max_len=MAX_LEN,
                             kv_layout="paged", kv_block_size=4))
        uids = [eng.submit(p, g, patch_embeds=e)
                for p, g, e in zip(prompts, gens, pe)]
        done = eng.run()
    assert [done[u] for u in uids] == expected


def test_config_pallas_attn_maps_to_pallas_paged():
    import dataclasses

    cfg = get_smoke_config("granite_8b")
    spec = dataclasses.replace(cfg, attn_impl="pallas").paged_attention_spec
    assert spec.impl == "pallas_paged"
    ops.validate(spec)
