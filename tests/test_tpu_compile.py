"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode cannot see tiling or memory-space refusals, so these tests
hand each kernel to the TPU compiler for a *described* v5e chip — no chip
attached, shapes only (``jax.ShapeDtypeStruct``).  Widths are granite-8b's
published ones: Hq 32, Hkv 8, head dim 128, KV block 16.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fixedpoint import DEFAULT_FORMAT
from repro.kernels.flash_star.kernel import flash_star_attention
from repro.kernels.paged_attention import paged_flash_attention
from repro.kernels.star_softmax.kernel import star_softmax_pallas

HQ, HKV, D, BS = 32, 8, 128, 16
# the serve tick's operands: 8 slots of ~1 K tokens over every layer's
# pages, [LAYERS, POOL, BS, HKV, D] (5.9 GB at float32, 1.5 GB at int8)
SLOTS, TABLE_W, LAYERS, POOL = 8, 68, 8, 5_600


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what it writes to the persistent
    # cache; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("kind", ["star", "exact"])
@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_paged_decode_compiles(one_chip, kv_dtype, kind):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    fmt = DEFAULT_FORMAT if kind == "star" else None
    pages = sds((LAYERS, POOL, BS, HKV, D), kv_dtype)
    shapes = [sds((SLOTS, HQ, D), "bfloat16"), pages, pages,
              sds((SLOTS, TABLE_W), "int32"), sds((SLOTS,), "int32"),
              sds((), "int32")]
    if kv_dtype == "int8":
        # scale pages of every layer (1.4 MB each): only the tables' pages
        # may reach SMEM (1 MiB on v5e), or this refuses
        shapes += [sds((LAYERS, POOL, HKV), "float32")] * 2

    def decode(q, kp, vp, tables, valid, layer, *scales):
        return paged_flash_attention(
            q, kp, vp, tables, valid, fmt=fmt, interpret=False,
            k_scale=scales[0] if scales else None,
            v_scale=scales[1] if scales else None, layer=layer,
        )

    compiled = _compile(decode, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["star", "exact"])
def test_flash_star_prefill_compiles(one_chip, kind):
    fmt = DEFAULT_FORMAT if kind == "star" else None
    t = 2048

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def prefill(q, k, v, info):
        return flash_star_attention(q, k, v, info, fmt=fmt, causal=True,
                                    interpret=False)

    compiled = _compile(
        prefill, sds((1, HQ, t, D), "bfloat16"), sds((1, HKV, t, D), "bfloat16"),
        sds((1, HKV, t, D), "bfloat16"), sds((2,), "int32"))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["gather", "histogram"])
def test_star_softmax_compiles(one_chip, mode):
    rows, d = 4096, 2048

    def softmax(x):
        return star_softmax_pallas(x, fmt=DEFAULT_FORMAT,
                                   use_histogram=mode == "histogram",
                                   interpret=False)

    compiled = _compile(
        softmax, jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()
