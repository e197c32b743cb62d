"""flash_star — fused blocked attention with the STAR softmax engine.

This is the paper's **vector-grained global pipeline** (§II, last ¶) in its
TPU-native form: instead of three crossbar engines pipelining QKᵀ → softmax
→ P·V per attention vector, one Pallas kernel walks KV blocks with the three
stages fused in VMEM; the Pallas grid's DMA double-buffering overlaps the
HBM→VMEM load of block *i+1* with the compute of block *i* — the crossbar
pipeline's overlap, realized by the TPU memory system.

STAR arithmetic is the integer-grid online form (DESIGN.md §2): scores are
snapped to the codebook grid once, the running max is an int32, the rescale
factor is a codebook entry, and the result equals the two-pass engine to
float32 rounding.

Grid: ``(B, Hq, num_q_blocks, num_kv_blocks)`` — KV innermost so the
``(m, s, acc)`` VMEM scratch carries across KV steps of one q block.
Causal / sliding-window / ragged-KV blocks are predicated off with
``pl.when`` (on real TPU this skips the MXU work of fully-masked blocks).

Beyond-paper: ``pv_int8=True`` quantizes P (already a ≤2^b-value codebook —
the paper's own observation) *and* V per block to int8 and runs P·V on the
int8 MXU path (2x bf16 MXU throughput on v5e, half the VMEM traffic for P).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixedpoint import GRID_SENTINEL, FixedPointFormat

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _kernel(
    info_ref,  # int32 [1 + B]: [q_offset, kv_valid_len_0, ...]
    q_ref,  # (1, 1, bq, D)
    k_ref,  # (1, 1, bk, D)
    v_ref,  # (1, 1, bk, D)
    o_ref,  # (1, 1, bq, D)
    m_scr,  # (bq,) int32 (star) / f32 (exact)
    s_scr,  # (bq,) f32
    acc_scr,  # (bq, D) f32
    *,
    fmt: Optional[FixedPointFormat],
    causal: bool,
    sliding_window: Optional[int],
    kv_len: int,
    sm_scale: float,
    pv_int8: bool,
):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    star = fmt is not None

    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    q_offset = info_ref[0]
    kv_valid = info_ref[1 + b]

    @pl.when(ik == 0)
    def _init():
        if star:
            m_scr[...] = jnp.full_like(m_scr, GRID_SENTINEL)
        else:
            m_scr[...] = jnp.full_like(m_scr, -1e30)
        s_scr[...] = jnp.zeros_like(s_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level skip: the whole KV block is masked out.
    row0 = iq * bq + q_offset  # absolute position of first q row
    col0 = ik * bk
    live = col0 < kv_valid
    if causal:
        live &= col0 <= row0 + (bq - 1)
    if sliding_window is not None:
        live &= (col0 + bk - 1) > (row0 - sliding_window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)

        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < jnp.minimum(kv_valid, kv_len)
        if causal:
            mask &= cols <= rows
        if sliding_window is not None:
            mask &= cols > rows - sliding_window

        if star:
            nl = fmt.num_levels
            scale_fp = jnp.float32(fmt.scale)
            jgrid = jnp.where(
                mask, jnp.round(s * scale_fp).astype(jnp.int32), GRID_SENTINEL
            )
            m_blk = jnp.max(jgrid, axis=-1)  # (bq,) int32
            m_old = m_scr[...]
            m_new = jnp.maximum(m_old, m_blk)
            shift = jnp.clip(m_new - m_old, 0, nl - 1)
            r = jnp.exp(-shift.astype(jnp.float32) / scale_fp)  # LUT entry
            kidx = jnp.clip(m_new[:, None] - jgrid, 0, nl - 1)
            p = jnp.exp(-kidx.astype(jnp.float32) / scale_fp)  # LUT entries
            p = jnp.where(mask, p, 0.0)
            m_scr[...] = m_new
        else:
            s = jnp.where(mask, s, -1e30)
            m_blk = jnp.max(s, axis=-1)
            m_old = m_scr[...]
            m_new = jnp.maximum(m_old, m_blk)
            r = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where(mask, p, 0.0)
            m_scr[...] = m_new

        if pv_int8:
            # P is a codebook: <= 2^b distinct values in (0, 1] -> int8
            # mantissas are near-lossless for the mass that matters.  V is
            # quantized per block with a dynamic scale.  P·V hits the int8
            # MXU path (2x bf16 throughput on v5e).
            p8 = jnp.round(p * 127.0).astype(jnp.int8)
            vf = v.astype(jnp.float32)
            vamax = jnp.maximum(jnp.max(jnp.abs(vf)), 1e-6)
            v8 = jnp.round(vf * (127.0 / vamax)).astype(jnp.int8)
            pv32 = jax.lax.dot_general(
                p8, v8, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            pv = pv32.astype(jnp.float32) * (vamax / (127.0 * 127.0))
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        s_scr[...] = s_scr[...] * r + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * r[:, None] + pv

    @pl.when(ik == nk - 1)
    def _finalize():
        den = s_scr[...]
        den = jnp.where(den <= 0.0, 1.0, den)
        o_ref[0, 0] = (acc_scr[...] / den[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fmt", "causal", "sliding_window", "sm_scale",
        "block_q", "block_k", "pv_int8", "interpret",
    ),
)
def flash_star_attention(
    q: jax.Array,  # [B, Hq, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,  # [B, Hkv, Tk, D]
    info: jax.Array,  # int32 [1 + B]: [q_offset, kv_valid_len per batch]
    *,
    fmt: Optional[FixedPointFormat],  # None -> exact softmax (baseline)
    causal: bool = True,
    sliding_window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    pv_int8: bool = False,
    interpret: Optional[bool] = None,  # None -> ops.platform decides
) -> jax.Array:
    """Fused attention, heads-major layout.  Returns [B, Hq, Tq, D]."""
    from repro.ops.platform import resolve_interpret  # ops imports kernels

    interpret = resolve_interpret(interpret)
    batch, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0, "GQA needs Hq % Hkv == 0"
    group = hq // hkv
    sm_scale = (d ** -0.5) if sm_scale is None else sm_scale

    bq = min(block_q, tq)
    bk = min(block_k, tk)
    pad_q = (-tq) % bq
    pad_k = (-tk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (tq + pad_q) // bq
    nk = (tk + pad_k) // bk

    star = fmt is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j, info: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, info: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, info: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j, info: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.int32 if star else jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            fmt=fmt,
            causal=causal,
            sliding_window=sliding_window,
            kv_len=tk,
            sm_scale=sm_scale,
            pv_int8=pv_int8,
        ),
        out_shape=jax.ShapeDtypeStruct((batch, hq, tq + pad_q, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(info, q, k, v)
    return out[:, :, :tq]
