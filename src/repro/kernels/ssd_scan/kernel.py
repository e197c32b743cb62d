"""Fused SSD (state-space duality) chunk scan as a Pallas TPU kernel.

Why a kernel: the pure-JAX chunk scan materializes the [Q, Q, H] intra-chunk
decay tensor and the running state in HBM every chunk — the dry-run shows
mamba2's memory term dominating its compute term by >100×.  Fusing one
chunk's intra-quadratic + inter-recurrence in VMEM (state lives in scratch
across the chunk grid) removes that traffic — the same insight as the
paper's vector-grained pipeline, applied to the attention-free mixer.

Grid: ``(batch, num_chunks)`` — chunks innermost so the ``[H, N, P]`` state
scratch carries the recurrence.  Per chunk (Q = chunk length):

  scores  = C Bᵀ                      (Q×Q, MXU)
  decay   = exp(ca_i - ca_j) masked   (VPU, never leaves VMEM)
  y_intra = (scores ⊙ decay_h) @ xdt  (MXU per head)
  y_inter = exp(ca) ⊙ (C @ h_prev)    (MXU)
  h_new   = exp(last) h_prev + Σ_j exp(last - ca_j) B_j xdtᵀ_j
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *, nheads: int):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)  # [Q, H, P]
    a = a_ref[0].astype(jnp.float32)  # [Q, H]
    bm = b_ref[0].astype(jnp.float32)  # [Q, N]
    cm = c_ref[0].astype(jnp.float32)  # [Q, N]
    q = x.shape[0]

    ca = jnp.cumsum(a, axis=0)  # [Q, H] inclusive
    last = ca[-1]  # [H]
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, K]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = rows >= cols

    hprev = h_scr[...]  # [H, N, P]
    # y_inter = exp(ca) * (C @ h_prev)  per head
    y_inter = jnp.einsum("qn,hnp->qhp", cm, hprev)
    y_inter = y_inter * jnp.exp(ca)[:, :, None]

    # y_intra: per head decay-masked score matmul
    decay = jnp.exp(ca[:, None, :] - ca[None, :, :])  # [Q, K, H]
    decay = jnp.where(tri[:, :, None], decay, 0.0)
    y_intra = jnp.einsum("qk,qkh,khp->qhp", scores, decay, x)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update
    w = jnp.exp(last[None, :] - ca)  # [Q, H] decay from j to chunk end
    s_c = jnp.einsum("qn,qhp,qh->hnp", bm, x, w)
    hnew = hprev * jnp.exp(last)[:, None, None] + s_c
    h_scr[...] = hnew

    @pl.when(ic == nc - 1)
    def _():
        hout_ref[0] = hnew.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    xdt: jax.Array,  # [B, T, H, P]
    a: jax.Array,  # [B, T, H]
    bmat: jax.Array,  # [B, T, N]
    cmat: jax.Array,  # [B, T, N]
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,  # None -> ops.platform decides
) -> Tuple[jax.Array, jax.Array]:
    from repro.ops.platform import resolve_interpret  # ops imports kernels

    interpret = resolve_interpret(interpret)
    b, t, h, p = xdt.shape
    n = bmat.shape[-1]
    pad = (-t) % chunk
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // chunk

    y, hout = pl.pallas_call(
        functools.partial(_kernel, nheads=h),
        out_shape=(
            jax.ShapeDtypeStruct((b, t + pad, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ),
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, h, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, chunk, h), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, h, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, h, n, p), lambda i, j: (i, 0, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((h, n, p), jnp.float32)],
        interpret=interpret,
    )(xdt, a, bmat, cmat)
    return y[:, :t], hout
