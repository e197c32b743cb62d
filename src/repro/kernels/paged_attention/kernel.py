"""paged_flash — gather-free paged-attention decode over a block-pool cache.

The gather adapters in ``repro.ops.impls`` re-materialize every slot's
whole KV window (``jnp.take`` over the page pool -> a dense
``[S, W*bs, Hkv, D]`` operand) before the flash kernel ever runs, so paged
decode pays dense-attention HBM traffic *plus* the gather.  This kernel is
the vLLM/TPU lineage answer: the per-slot block table rides in as a
**scalar-prefetch** operand, the grid walks ``(slot, kv_block)``, and each
grid step's BlockSpec index map dereferences the table —
``k_pages[tables[s, j]]`` — so the Pallas pipeline DMA-fetches exactly the
one page that step consumes.  No gathered operand exists at any point;
per-token HBM traffic scales with the slot's *live* length, not the pool
width.

Softmax accumulation is the flash_star online form (DESIGN.md §2): on the
STAR path scores snap to the fixed-point grid once, the running max is an
int32 grid index, and both the rescale factor and the probabilities are
codebook (LUT) entries, so the result matches the two-pass engine to
float32 rounding.  ``fmt=None`` runs the exact float32 online softmax.

Layout contract (mirrors ``repro.serve.paged``):

* ``q``          — ``[S, Hq, D]`` one decode token per slot;
* ``k/v_pages``  — ``[L, N, bs, Hkv, D]`` every layer's page pools, with
  ``layer`` naming the one to read (a single pool is a stack of one,
  ``pages[None]`` with ``layer=0``).  The serve tick passes the whole
  stack so that its in-place row writes never copy a layer out of it.
  Block 0 is the scratch page: free-slot writes land there, tables of
  retired slots point there;
* ``block_tables`` — ``[S, W]`` int32; logical row ``i`` of slot ``s``
  lives at ``(block_tables[s, i // bs], i % bs)``;
* ``kv_valid``   — ``[S]`` int32 ragged valid prefix per slot.  Ring
  (sliding-window) caches pass ``min(len, cache_t)`` exactly like the
  dense per-slot path — wrap-around changes *where* rows live (the table),
  never the mask, so the ring case needs no kernel support.

Grid ``(S, W)`` — KV blocks innermost so the ``(m, l, acc)`` VMEM scratch
carries across a slot's pages.  Each step fetches one whole page, every
KV head of it: the ``(1, bs, Hkv, D)`` block spans the pool's two minor
dims in full, which is what the TPU's tiling demands of a block at every
page dtype (a one-head ``(1, bs, 1, D)`` block would cut the second-minor
``Hkv`` axis below its 8-row tile).  The page flattens to
``(bs * Hkv, D)`` rows ordered ``(position, head)``, all ``Hq`` query
heads score against all of those rows in one MXU product, and a
block-diagonal mask keeps each query head on the rows of its own KV head
(``Hq // Hkv`` query heads share one — GQA).  The v5e compiler also takes
the per-KV-head form (the page as ``(Hkv, bs, D)``, one product batched
over KV heads), which does an ``Hkv``-th of the arithmetic, yet it ran
4-6% slower at granite-8b decode shapes on a v5e (PERF.md): its
``(group, bs)`` score tiles fill an eighth of a vreg each.  Steps whose
block lies past
``kv_valid`` are predicated off with ``pl.when`` AND their index map
clamps to the slot's last live page, so consecutive steps request the
same block and the Pallas pipeline elides the redundant DMA — masked tail
blocks cost neither MXU work nor HBM bandwidth.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixedpoint import GRID_SENTINEL, FixedPointFormat


def _row_scale(scale_ref, base, hkv: int, rows: int) -> jax.Array:
    """``(rows, 1)`` per-row dequant scale of one page: row ``r`` holds
    head ``r % hkv``, whose scale is the SMEM scalar ``scale_ref[base + h]``."""
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % hkv
    out = jnp.zeros((rows, 1), jnp.float32)
    for h in range(hkv):
        out = jnp.where(head == h, scale_ref[base + h], out)
    return out


def _kernel(
    tables_ref,  # int32 [S * W] scalar-prefetch block tables (row-major)
    valid_ref,  # int32 [S] ragged valid prefix per slot
    layer_ref,  # int32 [1] the pool stack's layer (read by the index maps)
    *refs,  # quantized: (ks, vs) live-page scales lead; then q/k/v/o + scratch
    fmt: Optional[FixedPointFormat],
    bs: int,
    hkv: int,
    sm_scale: float,
    quantized: bool,
):
    # Operand order past the three index operands:
    #   quantized: ks_ref, vs_ref [S * W * Hkv] f32  (scalar prefetch 4/5)
    #   always:    q_ref (1,Hq,D), k_ref (1,bs,Hkv,D), v_ref (1,bs,Hkv,D),
    #              o_ref (1,Hq,D), m/l/acc scratch
    del layer_ref
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    j = pl.program_id(1)
    nw = pl.num_programs(1)
    star = fmt is not None
    kv_valid = valid_ref[s]

    @pl.when(j == 0)
    def _init():
        if star:
            m_scr[...] = jnp.full_like(m_scr, GRID_SENTINEL)
        else:
            m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Whole-block skip: every row of page j is past the slot's valid
    # prefix (free slots, table tails).  The index map already pinned the
    # DMA to the last live page, so a skipped step moves no bytes.
    @pl.when(j * bs < kv_valid)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (Hq, D)
        hq, d = q.shape
        rows = bs * hkv
        # row r of the flattened page: position r // hkv, KV head r % hkv
        k = k_ref[0].astype(jnp.float32).reshape(rows, d)
        v = v_ref[0].astype(jnp.float32).reshape(rows, d)
        if quantized:
            # In-kernel dequant (DESIGN.md §13): the wrapper looked up each
            # live page's (block, head) scales through the table, so this
            # step's scales sit at (s, j) — the same codes * scale
            # expression the gather oracle evaluates.
            base = (s * nw + j) * hkv
            k = k * _row_scale(ks_ref, base, hkv, rows)
            v = v * _row_scale(vs_ref, base, hkv, rows)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (Hq, bs * Hkv)

        qi = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 0)
        ri = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 1)
        group = hq // hkv
        mask = (ri % hkv == qi // group) & (j * bs + ri // hkv < kv_valid)

        if star:
            nl = fmt.num_levels
            scale_fp = jnp.float32(fmt.scale)
            jgrid = jnp.where(
                mask, jnp.round(sc * scale_fp).astype(jnp.int32), GRID_SENTINEL
            )
            m_blk = jnp.max(jgrid, axis=-1, keepdims=True)  # (Hq, 1) int32
            m_old = m_scr[...]
            m_new = jnp.maximum(m_old, m_blk)
            shift = jnp.clip(m_new - m_old, 0, nl - 1)
            r = jnp.exp(-shift.astype(jnp.float32) / scale_fp)  # LUT entry
            kidx = jnp.clip(m_new - jgrid, 0, nl - 1)
            p = jnp.exp(-kidx.astype(jnp.float32) / scale_fp)  # LUT entries
        else:
            sc = jnp.where(mask, sc, -1e30)
            m_old = m_scr[...]
            m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1, keepdims=True))
            r = jnp.exp(m_old - m_new)
            p = jnp.exp(sc - m_new)
        p = jnp.where(mask, p, 0.0)
        m_scr[...] = m_new

        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (Hq, D): the mask zeroed every other head's rows
        l_scr[...] = l_scr[...] * r + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * r + pv

    @pl.when(j == nw - 1)
    def _finalize():
        den = l_scr[...]
        den = jnp.where(den <= 0.0, 1.0, den)  # free slot: emit zeros
        o_ref[0] = (acc_scr[...] / den).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("fmt", "sm_scale", "interpret")
)
def paged_flash_attention(
    q: jax.Array,  # [S, Hq, D] one decode token per slot
    k_pages: jax.Array,  # [L, N, bs, Hkv, D] every layer's page pool
    v_pages: jax.Array,  # same shape as k_pages
    block_tables: jax.Array,  # [S, W] int32 page ids
    kv_valid: jax.Array,  # [S] int32 valid prefix per slot
    *,
    layer: jax.Array,  # int32: which of the L pools to read
    fmt: Optional[FixedPointFormat],  # None -> exact online softmax
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,  # None -> ops.platform decides
    k_scale: Optional[jax.Array] = None,  # [L, N, Hkv] f32 dequant scales
    v_scale: Optional[jax.Array] = None,  # like k_scale
) -> jax.Array:
    """Gather-free paged decode attention.  Returns ``[S, Hq, D]``.

    With ``k_scale``/``v_scale`` the pages hold quantized codes
    (``core.kvquant`` — int8 or fp8_e4m3).  Only the scales of the pages
    the tables name are looked up (``[S, W, Hkv]``, a few KB), and they
    ride the scalar-prefetch path next to the block tables; each grid step
    dequantizes its one page in VMEM, so the ``[S, W*bs, Hkv, D]``
    gathered operand never exists at *any* precision (DESIGN.md §13).
    Scalar prefetch lands in SMEM, so its size follows the slots and the
    table width, never the pool.
    """
    from repro.ops.platform import resolve_interpret  # ops imports kernels

    s, hq, d = q.shape
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quantized = k_scale is not None
    _, n, bs, hkv, _ = k_pages.shape
    assert hq % hkv == 0, "GQA needs Hq % Hkv == 0"
    w = block_tables.shape[1]
    sm_scale = (d ** -0.5) if sm_scale is None else sm_scale

    # 1-D scalar-prefetch operands: SMEM pads a 2-D array's minor dim
    tables = block_tables.astype(jnp.int32).reshape(-1)
    valid = kv_valid.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_map(si, ji, *prefetch):
        del ji, prefetch
        return (si, 0, 0)

    def kv_map(si, ji, tables, valid, layer, *scales):
        # Clamp table lookups past the valid prefix to the slot's last
        # live page: consecutive masked steps then request the *same*
        # block, and the pipeline elides the DMA.  An all-free slot
        # (valid == 0) pins to table column 0 — the scratch page.
        del scales
        last = jnp.maximum((valid[si] + bs - 1) // bs - 1, 0)
        return (layer[0], tables[si * w + jnp.minimum(ji, last)], 0, 0, 0)

    kv_block = (pl.Squeezed(), 1, bs, hkv, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=(s, w),
        in_specs=[
            pl.BlockSpec((1, hq, d), q_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
        ],
        out_specs=pl.BlockSpec((1, hq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.int32 if fmt is not None else jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(
            _kernel, fmt=fmt, bs=bs, hkv=hkv, sm_scale=sm_scale,
            quantized=quantized,
        ),
        out_shape=jax.ShapeDtypeStruct((s, hq, d), q.dtype),
        grid_spec=grid_spec,
        interpret=resolve_interpret(interpret),
    )
    if quantized:
        def live(scale):  # [L, N, Hkv] -> [S * W * Hkv], the tables' pages
            pages = jax.lax.dynamic_index_in_dim(scale, layer[0], 0, False)
            return jnp.take(pages.astype(jnp.float32), tables, axis=0).reshape(-1)

        return call(tables, valid, layer, live(k_scale), live(v_scale),
                    q, k_pages, v_pages)
    return call(tables, valid, layer, q, k_pages, v_pages)
