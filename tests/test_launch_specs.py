"""Launch-layer units that do NOT need 512 devices: input specs for all
cells, the HLO collective parser, roofline math."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, all_cells, get_config, shapes_for
from repro.launch.roofline import (
    collective_bytes,
    model_flops,
    roofline_terms,
)
from repro.launch.specs import input_specs


def test_cell_enumeration():
    cells = all_cells()
    assert len(cells) == 33  # 10 x 3 + 3 long_500k
    assert ("mamba2_130m", "long_500k") in cells
    assert ("llama3_405b", "long_500k") not in cells  # full-attention skip
    assert ("mixtral_8x22b", "long_500k") in cells  # SWA caps the cache


@pytest.mark.parametrize("cell", all_cells(), ids=lambda c: f"{c[0]}-{c[1]}")
def test_input_specs_structure(cell):
    arch, shape_name = cell
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    kind, inputs = input_specs(cfg, shape)
    leaves = jax.tree.leaves(inputs)
    assert all(isinstance(x, jax.ShapeDtypeStruct) or isinstance(x, int) for x in leaves)
    if kind == "train":
        toks = inputs["batch"]["tokens"]
        assert toks.shape[0] == shape.global_batch
    elif kind == "decode":
        assert inputs["tokens"].shape == (shape.global_batch, 1)
        # the cache really is seq_len deep (or window/state capped)
        cache_leaves = jax.tree.leaves(inputs["cache"])
        assert len(cache_leaves) >= 2


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[128,1024]{1,0} all-gather(%p0), replica_groups={}, dimensions={1}
  %ar = f32[128,1024]{1,0} all-reduce(%ag), to_apply=%add
  %rs.1 = bf16[64,512]{1,0} reduce-scatter(%ar), dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %a2a = s8[32,32]{1,0} all-to-all(%p0), dimensions={0}
"""
    res = collective_bytes(hlo)
    by = res["by_op"]
    assert by["all-gather"] == 128 * 256 * 4
    assert by["all-reduce"] == 128 * 1024 * 4
    assert by["reduce-scatter"] == 128 * 1024 * 4  # operand %ar
    assert by["collective-permute"] == 128 * 256 * 4
    assert by["all-to-all"] == 128 * 256 * 4
    assert res["count"]["all-gather"] == 1


def test_collective_parser_skips_done_ops():
    hlo = """
  %p0 = f32[16,16]{1,0} parameter(0)
  %ags = (f32[16,16], f32[64,16]) all-gather-start(%p0), dimensions={0}
  %agd = f32[64,16]{1,0} all-gather-done(%ags)
"""
    res = collective_bytes(hlo)
    assert res["count"].get("all-gather", 0) == 1  # start counted, done not


def test_roofline_terms_math():
    t = roofline_terms(
        device_kind="TPU v5 lite",
        flops_per_dev=197e12, bytes_per_dev=819e9, coll_bytes_per_dev=0.0
    )
    assert t["t_compute_s"] == pytest.approx(1.0)
    assert t["t_memory_s"] == pytest.approx(1.0)
    assert t["dominant"] in ("compute", "memory")
    t2 = roofline_terms(device_kind="TPU v5 lite", flops_per_dev=1e12,
                        bytes_per_dev=1e9, coll_bytes_per_dev=1e12)
    assert t2["dominant"] == "collective"
    with pytest.raises(ValueError, match="TPU v9"):
        roofline_terms(device_kind="TPU v9", flops_per_dev=1.0,
                       bytes_per_dev=1.0, coll_bytes_per_dev=0.0)


def test_model_flops_train_vs_infer():
    assert model_flops(1e9, 0, 1000, "train") == 6e12
    assert model_flops(1e9, 5e8, 1000, "prefill") == 2 * 5e8 * 1000


def test_production_mesh_requires_devices():
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(RuntimeError):
        make_production_mesh()  # only 1 device in the test process


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env):
    """The environment's directory wins untouched; otherwise the fixed
    ``<checkout>/.jax_cache``."""
    import pathlib

    from repro.launch import compile_cache

    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.CHECKOUT_CACHE_DIR == checkout / ".jax_cache"
    was = jax.config.jax_compilation_cache_dir
    sentinel = "/unchanged"
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want, config = str(checkout / ".jax_cache"), str(checkout / ".jax_cache")
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
            want, config = env, sentinel  # JAX reads the variable itself
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == config
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
