"""Pallas TPU kernel for the STAR softmax engine.

Grid walks row tiles; each tile ``(block_rows, d)`` lives in VMEM.  Inside a
tile the engine stages map to TPU units (DESIGN.md §2):

  CAM max search   -> int32 row max over the quantized grid      (VPU)
  SUB + CAM match  -> k = clip(m - j, 0, L-1)                    (VPU)
  LUT crossbar     -> p = exp(-k / scale): codebook entry,
                      evaluated arithmetically on the VPU (bit-equal to the
                      table up to 1 ulp), or via one-hot @ lut on the MXU
                      when ``use_mxu_lut=True`` (the faithful crossbar
                      dataflow; costs FLOPs, saves nothing on TPU — kept for
                      dataflow validation)
  counter + VMM    -> denominator via histogram @ lut (MXU) when
                      ``use_histogram=True``, else a plain row sum (VPU)
  divider          -> reciprocal-multiply                        (VPU)

The quantized index tile is emitted alongside the probabilities when
``emit_indices=True`` so downstream int8 P·V consumers can reuse the CAM
match without requantizing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fixedpoint import FixedPointFormat
from repro.hwmodel import faults as faults_lib
from repro.hwmodel.faults import FaultModel


def _levels_column(nl: int) -> jax.Array:
    """``[0, 1, ..., nl-1]`` as an ``(nl, 1)`` float32 column.  Mosaic's
    iota yields integers only, so the LUT index is built in int32 and
    cast."""
    return jax.lax.broadcasted_iota(jnp.int32, (nl, 1), 0).astype(jnp.float32)


def _kernel(
    x_ref,
    o_ref,
    *,
    fmt: FixedPointFormat,
    use_histogram: bool,
    use_mxu_lut: bool,
):
    x = x_ref[...].astype(jnp.float32)  # (br, d)
    br, d = x.shape
    nl = fmt.num_levels
    scale = jnp.float32(fmt.scale)

    # CAM-at-input quantization onto the signed integer grid.
    j = jnp.round(x * scale).astype(jnp.int32)
    m = jnp.max(j, axis=-1, keepdims=True)  # CAM max search
    k = jnp.clip(m - j, 0, nl - 1)  # SUB + match index (>= 0)

    if use_mxu_lut:
        # Faithful crossbar dataflow: one-hot match matrix x LUT column (MXU).
        levels = jax.lax.broadcasted_iota(jnp.int32, (br, d, nl), 2)
        onehot = (levels == k[..., None]).astype(jnp.float32)
        lut = jnp.exp(-_levels_column(nl) / scale)
        p = jax.lax.dot_general(
            onehot.reshape(br * d, nl), lut,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(br, d)
    else:
        # VPU form: evaluate the codebook entry arithmetically.
        p = jnp.exp(-k.astype(jnp.float32) / scale)

    if use_histogram:
        # counter + VMM: histogram the match indices, then one small VMM.
        levels = jax.lax.broadcasted_iota(jnp.int32, (br, d, nl), 2)
        counts = jnp.sum((levels == k[..., None]).astype(jnp.float32), axis=1)
        lut = jnp.exp(-_levels_column(nl) / scale)
        den = jax.lax.dot_general(
            counts, lut, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (br, 1)
    else:
        den = jnp.sum(p, axis=-1, keepdims=True)

    o_ref[...] = (p / den).astype(o_ref.dtype)


def _kernel_faulty(
    x_ref,
    lut_ref,  # (L, 1) faulty numerator LUT column
    vmm_ref,  # (L, 1) faulty denominator VMM column
    remap_ref,  # (L, 1) CAM match remap (float-coded indices)
    o_ref,
    *,
    fmt: FixedPointFormat,
    use_histogram: bool,
):
    """Fault-injected variant: the LUT/VMM contents and the CAM remap are
    *runtime operands* (a seeded realization computed at trace time), so
    the codebook can no longer be evaluated arithmetically.  Every lookup
    is a one-hot matmul — the faithful crossbar dataflow, and exact (a
    single-nonzero dot reproduces the gathered entry bit-for-bit)."""
    x = x_ref[...].astype(jnp.float32)  # (br, d)
    br, d = x.shape
    nl = fmt.num_levels
    scale = jnp.float32(fmt.scale)

    j = jnp.round(x * scale).astype(jnp.int32)
    m = jnp.max(j, axis=-1, keepdims=True)  # CAM max search
    k = jnp.clip(m - j, 0, nl - 1)  # SUB + match index

    levels = jax.lax.broadcasted_iota(jnp.int32, (br, d, nl), 2)
    onehot = (levels == k[..., None]).astype(jnp.float32)
    # broken CAM rows match the nearest working row: k' = onehot(k) @ remap
    k2 = jax.lax.dot_general(
        onehot.reshape(br * d, nl), remap_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(br, d).astype(jnp.int32)
    onehot2 = (levels == k2[..., None]).astype(jnp.float32)
    p = jax.lax.dot_general(
        onehot2.reshape(br * d, nl), lut_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(br, d)

    if use_histogram:
        counts = jnp.sum(onehot2, axis=1)  # (br, nl)
        den = jax.lax.dot_general(
            counts, vmm_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (br, 1)
    else:
        den = jnp.sum(p, axis=-1, keepdims=True)

    den = jnp.where(den <= 0.0, 1.0, den)  # fully-stuck-off rows -> zeros
    o_ref[...] = (p / den).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fmt", "block_rows", "use_histogram", "use_mxu_lut", "interpret",
        "fault",
    ),
)
def star_softmax_pallas(
    x: jax.Array,
    *,
    fmt: FixedPointFormat,
    block_rows: int = 8,
    use_histogram: bool = False,
    use_mxu_lut: bool = False,
    interpret: Optional[bool] = None,
    fault: Optional[FaultModel] = None,
) -> jax.Array:
    """STAR softmax over the last axis of ``x`` (any leading shape).

    Rows are padded to a multiple of ``block_rows``; the full feature dim
    lives in one VMEM tile (use ``flash_star`` for attention-scale rows).
    ``interpret=None`` asks the platform (``ops.platform``).

    ``fault`` (static, hashable) switches to the fault-injected kernel:
    the seeded CAM/LUT/VMM realizations stream in as operands and the ADC
    denominator gain applies on the way out (DESIGN.md §9).
    """
    from repro.ops.platform import resolve_interpret  # ops imports kernels

    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    padded_rows = rows + pad
    grid = (padded_rows // block_rows,)
    out_shape = jax.ShapeDtypeStruct((padded_rows, d), jnp.float32)
    block = pl.BlockSpec((block_rows, d), lambda i: (i, 0))

    if faults_lib.is_null(fault):
        out = pl.pallas_call(
            functools.partial(
                _kernel, fmt=fmt, use_histogram=use_histogram,
                use_mxu_lut=use_mxu_lut,
            ),
            out_shape=out_shape,
            grid=grid,
            in_specs=[block],
            out_specs=block,
            interpret=interpret,
        )(x2)
        return out[:rows].reshape(orig_shape)

    nl = fmt.num_levels
    lut = faults_lib.faulty_exp_lut(fmt, fault, tag="softmax/lut")
    vmm = (
        faults_lib.faulty_exp_lut(fmt, fault, tag="softmax/vmm")
        if use_histogram
        else lut
    )
    remap = faults_lib.cam_remap(fmt, fault)
    if remap is None:
        remap = jnp.arange(nl, dtype=jnp.int32)
    table_spec = pl.BlockSpec((nl, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(
            _kernel_faulty, fmt=fmt, use_histogram=use_histogram
        ),
        out_shape=out_shape,
        grid=grid,
        in_specs=[block, table_spec, table_spec, table_spec],
        out_specs=block,
        interpret=interpret,
    )(
        x2,
        lut.reshape(nl, 1),
        vmm.reshape(nl, 1),
        remap.astype(jnp.float32).reshape(nl, 1),
    )
    out = out[:rows].reshape(orig_shape)
    if use_histogram:
        gain = faults_lib.adc_gain(fault)
        if gain is not None:
            # den' = den * gain  =>  out' = out / gain (gain applied to the
            # whole row uniformly — hoisting it out keeps the kernel clean)
            out = out / gain
    return out
