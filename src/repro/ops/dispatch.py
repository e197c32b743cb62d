"""Dispatch: spec -> (overrides, platform resolution, validation) -> backend.

The resolution order, outermost-wins:

1. the spec itself (or the op's default spec when ``spec=None``);
2. call-site keyword overrides (any spec field, e.g. ``causal=True`` or
   ``impl="pallas"``), applied via ``dataclasses.replace``;
3. active :func:`repro.ops.use` frames (impl / interpret retargeting —
   inner frames win over outer, and over the spec: that is their purpose);
4. ``interpret=None`` resolves to the detected platform's default.

The resolved spec is capability-validated against the selected backend
before the call, so mismatches fail with an actionable error naming the
field, the backend's supported values, and the impls that do support it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax

from repro.obs.metrics import default_registry as _obs_registry
from repro.ops import registry
from repro.ops.guard import Guard, as_guard
from repro.ops.platform import resolve_interpret
from repro.ops.registry import Backend, OpDispatchError
from repro.ops.specs import (
    AttentionSpec,
    MatmulSpec,
    PagedAttentionSpec,
    ScanSpec,
    SoftmaxSpec,
    Spec,
)

DEFAULT_SOFTMAX = SoftmaxSpec()
DEFAULT_ATTENTION = AttentionSpec()
DEFAULT_PAGED_ATTENTION = PagedAttentionSpec()
DEFAULT_MATMUL = MatmulSpec()
DEFAULT_SSD_SCAN = ScanSpec()


def resolve(spec: Spec, **overrides: Any) -> Tuple[Backend, Spec]:
    """Apply overrides and ``use()`` frames, pick and validate the backend."""
    if overrides:
        try:
            spec = dataclasses.replace(spec, **overrides)
        except TypeError as exc:
            fields = [f.name for f in dataclasses.fields(spec)]
            raise OpDispatchError(
                f"invalid {type(spec).__name__} override(s) "
                f"{sorted(overrides)}: valid fields are {fields}"
            ) from exc
    ctx = registry.active_overrides(spec.op)
    updates: dict = {}
    if "impl" in ctx:
        updates["impl"] = ctx["impl"]
    updates["interpret"] = resolve_interpret(ctx.get("interpret", spec.interpret))
    spec = dataclasses.replace(spec, **updates)
    backend = registry.get(spec.op, spec.impl)
    registry.validate(backend, spec)
    # per-(op, resolved impl, resolved interpret) dispatch counter
    # (DESIGN.md §10).  Counts *dispatches*: for a jitted call site that is
    # trace time, so a cached retrace-free loop counts once — which is
    # itself a useful signal.  The interpret label shows a Pallas kernel
    # that fell back to the interpreter on a chip.
    _obs_registry().counter("ops.dispatch.calls").inc(
        op=spec.op, impl=backend.impl, interpret=spec.interpret
    )
    return backend, spec


def validate(spec: Spec, **overrides: Any) -> Spec:
    """Resolve + capability-check a spec without executing anything.

    Launchers call this at config time so a spec the registry cannot serve
    fails before any lowering starts.  Returns the resolved spec.
    """
    return resolve(spec, **overrides)[1]


def softmax(
    x: jax.Array,
    spec: Optional[SoftmaxSpec] = None,
    *,
    where: Optional[jax.Array] = None,
    axis: int = -1,
    guard: Optional[Guard] = None,
    **overrides: Any,
) -> jax.Array:
    """Softmax over ``axis`` through the registered backend for ``spec``.

    ``guard`` (an :class:`~repro.ops.guard.AccuracyGuard` or
    :class:`~repro.ops.guard.GuardConfig`) wraps the call in the accuracy
    guard: sampled comparison against the exact oracle, fallback to a clean
    backend on tolerance violation.  Eager call sites only.
    """
    backend, spec = resolve(spec if spec is not None else DEFAULT_SOFTMAX, **overrides)
    g = as_guard(guard)
    if g is not None:
        return g.softmax(backend, spec, x, where=where, axis=axis)
    return backend.fn(spec, x, where=where, axis=axis)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    spec: Optional[AttentionSpec] = None,
    *,
    q_offset: Any = 0,
    kv_valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    **overrides: Any,
) -> jax.Array:
    """Attention (q [B,Tq,Hq,D], k/v [B,Tk,Hkv,D]) -> [B,Tq,Hq,D]."""
    backend, spec = resolve(
        spec if spec is not None else DEFAULT_ATTENTION, **overrides
    )
    return backend.fn(
        spec, q, k, v, q_offset=q_offset, kv_valid_len=kv_valid_len, scale=scale
    )


def paged_attention(
    q: jax.Array,  # [S, Tq, Hq, D] (decode: Tq == 1)
    k_pages: jax.Array,  # [L, num_blocks, block_size, Hkv, D]
    v_pages: jax.Array,  # same shape as k_pages
    block_tables: jax.Array,  # [S, W] int32 block ids per sequence
    spec: Optional[PagedAttentionSpec] = None,
    *,
    kv_valid_len: jax.Array,  # [S] ragged valid prefix per sequence
    kv_len: Optional[int] = None,  # logical gathered length (<= W * block_size)
    scale: Optional[float] = None,
    layer: jax.Array,  # int32: which of the L pools to read
    kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,  # [L, N, Hkv] each
    **overrides: Any,
) -> jax.Array:
    """Paged-KV decode attention: gather each sequence's blocks through its
    table, attend over the ragged valid prefix.  Returns ``[S, Tq, Hq, D]``.

    ``kv_len`` trims the gathered buffer to the logical cache length when
    the block grid overshoots it (``W * block_size`` rows gathered, only
    ``kv_len`` meaningful) so the operands — and hence the numerics — match
    the dense per-slot cache exactly.

    ``kv_scales`` carries the per-(block, head) dequant scale pages
    ``(k_scale, v_scale)`` when the pool stores quantized codes
    (``spec.kv_dtype != "fp32"`` — DESIGN.md §13); required then,
    forbidden otherwise, so a layout/spec mismatch fails loudly here
    instead of decoding garbage.

    Pools are stacked over layers (``[L, num_blocks, ...]`` pages,
    ``[L, num_blocks, Hkv]`` scales) and ``layer`` names the one to read,
    in place: the serve tick carries the whole stack.  A single pool is a
    stack of one (``pages[None]``, ``layer=0``).
    """
    backend, spec = resolve(
        spec if spec is not None else DEFAULT_PAGED_ATTENTION, **overrides
    )
    if (spec.kv_dtype != "fp32") != (kv_scales is not None):
        raise OpDispatchError(
            f"kv_dtype={spec.kv_dtype!r} but kv_scales "
            f"{'missing' if kv_scales is None else 'supplied'}: quantized "
            "page pools must pass their (k_scale, v_scale) pages and fp32 "
            "pools must not (DESIGN.md §13)"
        )
    return backend.fn(
        spec,
        q,
        k_pages,
        v_pages,
        block_tables,
        kv_valid_len=kv_valid_len,
        kv_len=kv_len,
        scale=scale,
        kv_scales=kv_scales,
        layer=layer,
    )


def matmul(
    x: jax.Array,
    w: jax.Array,
    spec: Optional[MatmulSpec] = None,
    *,
    guard: Optional[Guard] = None,
    **overrides: Any,
) -> jax.Array:
    """x [M, K] @ w [K, N] through the registered backend for ``spec``.

    ``guard`` as in :func:`softmax` (matmul uses a relative max-abs error
    metric against the exact f32 product).
    """
    backend, spec = resolve(spec if spec is not None else DEFAULT_MATMUL, **overrides)
    g = as_guard(guard)
    if g is not None:
        return g.matmul(backend, spec, x, w)
    return backend.fn(spec, x, w)


def ssd_scan(
    xdt: jax.Array,
    a: jax.Array,
    bmat: jax.Array,
    cmat: jax.Array,
    spec: Optional[ScanSpec] = None,
    **overrides: Any,
):
    """Fused SSD chunk scan: (y [B,T,H,P], final state [B,H,N,P])."""
    backend, spec = resolve(spec if spec is not None else DEFAULT_SSD_SCAN, **overrides)
    return backend.fn(spec, xdt, a, bmat, cmat)
