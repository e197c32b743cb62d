"""Pallas kernel for the crossbar MatMul engine model (ref.py semantics).

Grid ``(M/bm, N/tile_cols, K/tile_rows)`` — K innermost so the f32
accumulator scratch carries quantized partial sums across crossbar K-tiles,
exactly like the digital accumulator behind the ADCs.  The per-tile ADC
step array is computed in ops.py (calibration) and streamed per grid cell.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.crossbar_matmul.ref import CrossbarSpec, DEFAULT_SPEC


def _kernel(x_ref, w_ref, step_ref, off_ref, o_ref, acc, *, adc_levels: int):
    kt = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kt == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    partial = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    st = step_ref[0, 0]
    # per-tile ADC input-referred offset (fault injection; zero when ideal)
    code = partial / st + off_ref[0, 0]
    adc = jnp.clip(jnp.round(code), -adc_levels, adc_levels) * st
    acc[...] += adc

    @pl.when(kt == nk - 1)
    def _():
        o_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("spec", "block_m", "interpret"))
def crossbar_matmul_pallas(
    xq: jax.Array,  # int8/int32 quantized activations [M, K], K % tile_rows == 0
    wq: jax.Array,  # quantized weights [K, N], N % tile_cols == 0 (f32 if faulty)
    step: jax.Array,  # f32 [Kt, Nt] ADC step per crossbar tile
    offsets: jax.Array | None = None,  # f32 [Kt, Nt] ADC offsets in LSB (faults)
    *,
    spec: CrossbarSpec = DEFAULT_SPEC,
    block_m: int = 128,
    interpret: Optional[bool] = None,  # None -> ops.platform decides
) -> jax.Array:
    from repro.ops.platform import resolve_interpret  # ops imports kernels

    interpret = resolve_interpret(interpret)
    m, kdim = xq.shape
    _, n = wq.shape
    ktiles = kdim // spec.tile_rows
    ntiles = n // spec.tile_cols
    if offsets is None:
        offsets = jnp.zeros((ktiles, ntiles), jnp.float32)
    bm = min(block_m, m)
    pad_m = (-m) % bm
    if pad_m:
        xq = jnp.pad(xq, ((0, pad_m), (0, 0)))
    mt = (m + pad_m) // bm

    out = pl.pallas_call(
        functools.partial(_kernel, adc_levels=spec.adc_levels),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n), jnp.float32),
        grid=(mt, ntiles, ktiles),
        in_specs=[
            pl.BlockSpec((bm, spec.tile_rows), lambda i, j, k: (i, k)),
            pl.BlockSpec((spec.tile_rows, spec.tile_cols), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, spec.tile_cols), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, spec.tile_cols), jnp.float32)],
        interpret=interpret,
    )(xq, wq, step, offsets)
    return out[:m]
