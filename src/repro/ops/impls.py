"""Built-in backends: the registry entries shipped with the repo.

Importing this module (done by ``repro.ops``) registers every built-in
implementation.  Each backend is a thin adapter from the spec contract to
an existing engine — the pure-jnp oracles in ``repro.core``, plain XLA
ops, the Pallas kernels in ``repro.kernels``, or the RRAM behavioural
model.  Numerics live in those modules; this file only routes.

Adding a backend is one call::

    from repro.ops import register

    register(
        "softmax", "my_impl", my_fn,
        capabilities={"kind": ("star",), "mode": ("gather", "histogram")},
        description="...",
    )

where ``my_fn(spec, x, *, where, axis)`` receives the resolved
:class:`~repro.ops.specs.SoftmaxSpec` plus the runtime arrays.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import kvquant
from repro.core.attention import (
    NEG_INF,
    SoftmaxConfig,
    attention as full_attention,
    blocked_attention,
)
from repro.core.star_softmax import exact_softmax, star_softmax, star_softmax_ste
from repro.hwmodel import faults as faults_lib
from repro.kernels.crossbar_matmul.kernel import crossbar_matmul_pallas
from repro.kernels.crossbar_matmul.ref import (
    _pad_to,
    adc_step,
    apply_weight_faults,
    quantize_operands,
)
from repro.kernels.flash_star.kernel import flash_star_attention
from repro.kernels.paged_attention.kernel import paged_flash_attention
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.star_softmax.kernel import star_softmax_pallas
from repro.ops.registry import CapabilityError, register
from repro.ops.specs import (
    AttentionSpec,
    MatmulSpec,
    PagedAttentionSpec,
    ScanSpec,
    SoftmaxSpec,
)

# ---------------------------------------------------------------------------
# softmax


def _softmax_reference(
    spec: SoftmaxSpec,
    x: jax.Array,
    *,
    where: Optional[jax.Array] = None,
    axis: int = -1,
) -> jax.Array:
    if spec.kind == "exact":
        if where is not None:
            x = jnp.where(where, x, NEG_INF)
        return exact_softmax(x, axis=axis)
    if spec.kind == "star_ste":
        if where is not None:
            # NEG_INF quantizes to the deepest LUT row (probability ~ 0).
            x = jnp.where(where, x, NEG_INF)
        return star_softmax_ste(x, spec.fmt, axis, spec.mode, spec.fault)
    return star_softmax(
        x, spec.fmt, axis=axis, mode=spec.mode, where=where, fault=spec.fault
    )


def _softmax_xla(
    spec: SoftmaxSpec,
    x: jax.Array,
    *,
    where: Optional[jax.Array] = None,
    axis: int = -1,
) -> jax.Array:
    if where is not None:
        x = jnp.where(where, x, NEG_INF)
    return jax.nn.softmax(x, axis=axis)


def _softmax_pallas(
    spec: SoftmaxSpec,
    x: jax.Array,
    *,
    where: Optional[jax.Array] = None,
    axis: int = -1,
) -> jax.Array:
    if where is not None:
        raise CapabilityError(
            "softmax backend 'pallas' does not take a `where` mask (the "
            "kernel streams dense row tiles); mask upstream or use "
            "impl='reference'"
        )
    moved = axis % x.ndim != x.ndim - 1
    if moved:
        x = jnp.moveaxis(x, axis, -1)
    out = star_softmax_pallas(
        x,
        fmt=spec.fmt,
        block_rows=spec.block_rows,
        use_histogram=spec.mode == "histogram",
        use_mxu_lut=spec.mode == "onehot",
        interpret=spec.interpret,
        fault=spec.fault,
    )
    if moved:
        out = jnp.moveaxis(out, -1, axis)
    return out


register(
    "softmax",
    "reference",
    _softmax_reference,
    description="pure-jnp STAR engine / FP oracle (core.star_softmax)",
)
register(
    "softmax",
    "xla",
    _softmax_xla,
    capabilities={"kind": ("exact",), "fault": (None,)},
    description="jax.nn.softmax — the exact FP path, no quantization",
)
register(
    "softmax",
    "pallas",
    _softmax_pallas,
    capabilities={"kind": ("star",)},
    description="fused row-tile TPU kernel (kernels.star_softmax)",
)


# ---------------------------------------------------------------------------
# attention


def _attention_reference(
    spec: AttentionSpec,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_offset=0,
    kv_valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    return full_attention(
        q,
        k,
        v,
        softmax=SoftmaxConfig.from_spec(spec.softmax),
        causal=spec.causal,
        sliding_window=spec.sliding_window,
        q_offset=q_offset,
        kv_valid_len=kv_valid_len,
        scale=scale,
    )


def _attention_xla(
    spec: AttentionSpec,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_offset=0,
    kv_valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    # KV-block scanning is for long score rows.  For decode (tq == 1) it is
    # pure overhead — and with an SP-sharded cache the per-block re-slicing
    # forces XLA into involuntary resharding of the whole cache every layer
    # (the §Perf decode finding); the materialized einsum keeps the cache
    # sharding intact and lets the partial softmax reduce with one psum.
    # Under faults the online-rescale identity lut[a]*lut[b] == lut[a+b]
    # does not hold, so faulty calls always take the materialized path —
    # which also makes xla bit-identical to reference under any FaultModel.
    if (
        q.shape[1] == 1
        or k.shape[1] <= spec.block_kv
        or spec.softmax.fault is not None
    ):
        return _attention_reference(
            spec, q, k, v, q_offset=q_offset, kv_valid_len=kv_valid_len, scale=scale
        )
    return blocked_attention(
        q,
        k,
        v,
        softmax=SoftmaxConfig.from_spec(spec.softmax),
        causal=spec.causal,
        sliding_window=spec.sliding_window,
        q_offset=q_offset,
        kv_valid_len=kv_valid_len,
        scale=scale,
        block_size=spec.block_kv,
    )


def _attention_pallas(
    spec: AttentionSpec,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_offset=0,
    kv_valid_len: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    # Layout adapter: framework-native [B, T, H, D] -> the kernel's
    # [B, H, T, D], with (q_offset, per-batch valid lengths) packed into the
    # kernel's info vector.  The fused kernel always uses the arithmetic-LUT
    # dataflow; ``spec.softmax.mode`` is a dataflow hint for the unfused
    # engines and is ignored here.
    b, _, _, _ = q.shape
    tk = k.shape[1]
    if kv_valid_len is None:
        kv_valid_len = jnp.full((b,), tk, dtype=jnp.int32)
    info = jnp.concatenate(
        [jnp.asarray(q_offset, jnp.int32).reshape(1), kv_valid_len.astype(jnp.int32)]
    )
    qh = jnp.transpose(q, (0, 2, 1, 3))
    kh = jnp.transpose(k, (0, 2, 1, 3))
    vh = jnp.transpose(v, (0, 2, 1, 3))
    out = flash_star_attention(
        qh,
        kh,
        vh,
        info,
        fmt=spec.softmax.fmt,  # None for the exact kind
        causal=spec.causal,
        sliding_window=spec.sliding_window,
        sm_scale=scale,
        block_q=spec.block_q,
        block_k=spec.block_k,
        pv_int8=spec.pv_int8,
        interpret=spec.interpret,
    )
    return jnp.transpose(out, (0, 2, 1, 3))


register(
    "attention",
    "reference",
    _attention_reference,
    capabilities={"pv_int8": (False,)},
    description="whole-operand attention, scores materialized (core.attention)",
)
register(
    "attention",
    "xla",
    _attention_xla,
    capabilities={"pv_int8": (False,)},
    description="online-blocked lax.scan pipeline (falls back to the "
    "materialized path for short rows / single-token decode)",
)
register(
    "attention",
    "pallas",
    _attention_pallas,
    # online-rescale kernel: no per-cell fault path (see DESIGN.md §9)
    capabilities={"softmax.kind": ("star", "exact"), "softmax.fault": (None,)},
    description="fused flash_star TPU kernel (kernels.flash_star)",
)
register(
    "attention",
    "paged",
    _attention_xla,
    capabilities={"pv_int8": (False,)},
    description="paged KV-cache marker impl: dense invocations (prefill, "
    "lockstep) run the xla pipeline; the serve stack reads this impl as "
    "'use the block-pool cache' and routes decode through the "
    "paged_attention op (ops.use(attention='paged') flips both at once)",
)


# ---------------------------------------------------------------------------
# paged attention (block-pool KV cache decode — DESIGN.md §8)


def _gather_pages(
    k_pages: jax.Array,  # [N, bs, Hkv, D]
    v_pages: jax.Array,
    block_tables: jax.Array,  # [S, W] int32
    kv_len: Optional[int],
    kv_scales: Optional[tuple] = None,  # (k_scale, v_scale), each [N, Hkv]
) -> tuple:
    """Concatenate each sequence's blocks: -> dense [S, kv_len, Hkv, D].

    Logical row ``i`` lives at ``(table[i // bs], i % bs)`` (the
    serve.paged layout invariant), so reshaping the gathered blocks
    reproduces the dense per-slot cache row exactly; rows past ``kv_len``
    (block-grid overshoot) are dropped, rows past the caller's
    ``kv_valid_len`` are masked downstream.

    With ``kv_scales`` the pages hold quantized codes: each gathered block
    is dequantized through its own (block, head) scale — the same
    ``codes.astype(f32) * scale`` expression the paged kernel evaluates in
    place, so this gathered view is the kernel's dequant *oracle*
    (DESIGN.md §13).
    """
    s, w = block_tables.shape
    n, bs, hkv, d = k_pages.shape
    flat = block_tables.reshape(-1)
    kd = jnp.take(k_pages, flat, axis=0)
    vd = jnp.take(v_pages, flat, axis=0)
    if kv_scales is not None:
        k_scale, v_scale = kv_scales
        ks = jnp.take(k_scale, flat, axis=0)[:, None, :, None]  # [S*W,1,Hkv,1]
        vs = jnp.take(v_scale, flat, axis=0)[:, None, :, None]
        kd = kvquant.decode(kd, ks)
        vd = kvquant.decode(vd, vs)
    kd = kd.reshape(s, w * bs, hkv, d)
    vd = vd.reshape(s, w * bs, hkv, d)
    if kv_len is not None and kv_len < w * bs:
        kd = kd[:, :kv_len]
        vd = vd[:, :kv_len]
    return kd, vd


def _paged_dense_spec(spec: PagedAttentionSpec, impl: str) -> AttentionSpec:
    # Ragged valid lengths subsume causality for decode (DESIGN.md §6):
    # the gathered call is causal=False + kv_valid_len, like the dense
    # per-slot path.
    return AttentionSpec(
        impl=impl,
        softmax=spec.softmax,
        causal=False,
        ragged=True,
        block_q=spec.block_q,
        block_k=spec.block_k,
        interpret=spec.interpret,
    )


def _make_paged_backend(impl: str, dense_fn):
    """Adapter shared by every paged backend: gather the page pool through
    the block tables (in XLA — scatter/gather is not MXU work), then hand
    the dense view plus the ragged valid lengths to the matching dense
    attention backend (the pallas one packs them into the fused kernel's
    info vector)."""

    def fn(
        spec: PagedAttentionSpec,
        q: jax.Array,
        k_pages: jax.Array,
        v_pages: jax.Array,
        block_tables: jax.Array,
        *,
        kv_valid_len: jax.Array,
        kv_len: Optional[int] = None,
        scale: Optional[float] = None,
        kv_scales: Optional[tuple] = None,
        layer: jax.Array,
    ) -> jax.Array:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if kv_scales is not None:
            kv_scales = tuple(sc[layer] for sc in kv_scales)
        kd, vd = _gather_pages(k_pages, v_pages, block_tables, kv_len, kv_scales)
        return dense_fn(
            _paged_dense_spec(spec, impl),
            q,
            kd,
            vd,
            kv_valid_len=kv_valid_len,
            scale=scale,
        )

    return fn


register(
    "paged_attention",
    "reference",
    _make_paged_backend("reference", _attention_reference),
    capabilities={"kv_dtype": kvquant.KV_DTYPES},
    description="block-table gather + whole-operand ragged decode "
    "(core.attention); quantized pools dequantize at gather time — the "
    "paged kernel's dequant oracle",
)
register(
    "paged_attention",
    "xla",
    _make_paged_backend("xla", _attention_xla),
    capabilities={"kv_dtype": kvquant.KV_DTYPES},
    description="block-table gather via jnp.take + the online-blocked "
    "dense pipeline over ragged valid lengths (dequant oracle for "
    "quantized pools)",
)
register(
    "paged_attention",
    "pallas",
    _make_paged_backend("pallas", _attention_pallas),
    # online-rescale kernel: no per-cell fault path (see DESIGN.md §9)
    capabilities={
        "softmax.kind": ("star", "exact"),
        "softmax.fault": (None,),
        "kv_dtype": kvquant.KV_DTYPES,
    },
    description="block-table gather + fused flash_star kernel with the "
    "ragged-length info vector (kernels.flash_star)",
)


def _paged_pallas_paged(
    spec: PagedAttentionSpec,
    q: jax.Array,  # [S, Tq(=1), Hq, D]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    *,
    kv_valid_len: jax.Array,
    kv_len: Optional[int] = None,
    scale: Optional[float] = None,
    kv_scales: Optional[tuple] = None,
    layer: jax.Array,
) -> jax.Array:
    """Gather-free decode: the kernel walks the block table in place."""
    if q.shape[1] != 1:
        raise CapabilityError(
            "paged_attention backend 'pallas_paged' is a decode kernel "
            f"(one query token per slot); got Tq={q.shape[1]}. Use a "
            "gather backend for multi-token paged queries."
        )
    valid = kv_valid_len.astype(jnp.int32)
    if kv_len is not None:
        # ring caches: the live window is the valid prefix of the buffer
        valid = jnp.minimum(valid, jnp.int32(kv_len))
    k_scale, v_scale = kv_scales if kv_scales is not None else (None, None)
    out = paged_flash_attention(
        q[:, 0],
        k_pages,
        v_pages,
        block_tables,
        valid,
        fmt=spec.softmax.fmt,  # None for the exact kind
        sm_scale=scale,
        interpret=spec.interpret,
        k_scale=k_scale,
        v_scale=v_scale,
        layer=layer,
    )
    return out[:, None]


register(
    "paged_attention",
    "pallas_paged",
    _paged_pallas_paged,
    # same fused-kernel envelope as flash_star: no per-cell fault path
    capabilities={
        "softmax.kind": ("star", "exact"),
        "softmax.fault": (None,),
        "kv_dtype": kvquant.KV_DTYPES,
    },
    description="gather-free scalar-prefetch decode kernel: the grid "
    "walks (slot, kv_block) and DMA-fetches only table-named "
    "pages; quantized pools dequantize in-kernel with the scale pages "
    "riding scalar prefetch (kernels.paged_attention)",
)


def paged_gather_bytes(
    impl: str,
    *,
    table_width: int,
    block_size: int,
    live_lens,
    num_kv_heads: int,
    head_dim: int,
    dtype_bytes: int = 4,
    scale_bytes_per_block: int = 0,
) -> int:
    """Counted K+V bytes one paged decode step reads from the page pool.

    The gather adapters (``reference``/``xla``/``pallas``) materialize
    every slot's whole table window — ``S * W * bs`` rows — before the
    dense kernel runs.  ``pallas_paged`` DMA-fetches only each slot's live
    pages: ``sum(ceil(live / bs)) * bs`` rows (free slots still touch the
    one clamped page, matching the kernel's DMA-elision behaviour).  This
    is the interpret-normalized traffic model behind
    ``gather_bytes_per_token`` in ``kv_stats``/benchmarks — a counted
    quantity, not a measurement.

    ``dtype_bytes`` is the page-pool leaf itemsize (1 for int8/fp8 codes);
    ``scale_bytes_per_block`` adds the K+V scale-page bytes a quantized
    layout reads per touched block (0 for fp32 — DESIGN.md §13).
    """
    row_bytes = 2 * num_kv_heads * head_dim * dtype_bytes  # K and V
    lens = [int(x) for x in live_lens]
    if impl == "pallas_paged":
        blocks = sum(max(-(-live // block_size), 1) for live in lens)
    else:
        blocks = len(lens) * table_width
    return blocks * (block_size * row_bytes + scale_bytes_per_block)


def materializes(fn, shape, *args) -> bool:
    """True if any intermediate of ``fn(*args)``'s jaxpr — nested jaxprs
    (jit, scan, pallas bodies) included — has exactly ``shape``.

    The structural form of the gather-free claim: ``pallas_paged`` must
    never build the ``[S, W*bs, Hkv, D]`` gathered window that every
    gather adapter does."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    shape = tuple(shape)

    def walk(jaxpr) -> bool:
        for eqn in jaxpr.eqns:
            if any(getattr(v.aval, "shape", None) == shape for v in eqn.outvars):
                return True
            for val in eqn.params.values():
                for item in val if isinstance(val, (tuple, list)) else (val,):
                    if isinstance(item, ClosedJaxpr):
                        item = item.jaxpr
                    if isinstance(item, Jaxpr) and walk(item):
                        return True
        return False

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


# ---------------------------------------------------------------------------
# matmul


def _matmul_xla(spec: MatmulSpec, x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w)


def _matmul_hwmodel(spec: MatmulSpec, x: jax.Array, w: jax.Array) -> jax.Array:
    """x [M, K] @ w [K, N] through the RRAM crossbar behavioural model.

    With a ``spec.fault``, the stored weights pick up seeded cell faults
    (float32 — off the int grid by construction) and each tile's ADC an
    input-referred offset; calibration (``adc_step``) observes the faulty
    array, as a deployed design would.
    """
    xbar = spec.crossbar
    n = w.shape[1]
    (xq, sx), (wq, sw) = quantize_operands(x, w, xbar)
    xq = _pad_to(xq, 1, xbar.tile_rows)
    wq = _pad_to(_pad_to(wq, 0, xbar.tile_rows), 1, xbar.tile_cols)
    wq = apply_weight_faults(wq, xbar, spec.fault)
    step = adc_step(xq, wq, xbar, spec.ranging)
    offsets = None
    if spec.fault is not None:
        kt = xq.shape[1] // xbar.tile_rows
        nt = wq.shape[1] // xbar.tile_cols
        offsets = faults_lib.adc_tile_offsets(spec.fault, (kt, nt))
    out = crossbar_matmul_pallas(
        xq.astype(jnp.int8) if xbar.weight_bits <= 8 else xq,
        wq if spec.fault is not None
        else (wq.astype(jnp.int8) if xbar.weight_bits <= 8 else wq),
        step,
        offsets,
        spec=xbar,
        block_m=spec.block_m,
        interpret=spec.interpret,
    )
    return out[:, :n] * (sx * sw)


register(
    "matmul",
    "xla",
    _matmul_xla,
    capabilities={"fault": (None,)},
    description="native MXU matmul — the performance path",
)
register(
    "matmul",
    "hwmodel",
    _matmul_hwmodel,
    description="RRAM crossbar behavioural model: 8-bit operands on "
    "tile_rows x tile_cols crossbars through a 5-bit ADC "
    "(kernels.crossbar_matmul)",
)


# ---------------------------------------------------------------------------
# ssd_scan (mamba2 fused mixer — no softmax, same dispatch machinery)


def _ssd_scan_pallas(spec: ScanSpec, xdt, a, bmat, cmat):
    return ssd_scan_pallas(
        xdt, a, bmat, cmat, chunk=spec.chunk, interpret=spec.interpret
    )


def _ssd_scan_reference(spec: ScanSpec, xdt, a, bmat, cmat):
    # Lazy import: the reference delegates to the model's own chunked SSD
    # (repro.models imports repro.ops at module load — importing it here
    # at call time keeps the layering acyclic).
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    return ssd_scan_ref(xdt, a, bmat, cmat, chunk=spec.chunk)


register(
    "ssd_scan",
    "pallas",
    _ssd_scan_pallas,
    description="fused SSD chunk-scan TPU kernel (kernels.ssd_scan)",
)
register(
    "ssd_scan",
    "reference",
    _ssd_scan_reference,
    description="pure-jnp chunked SSD oracle (models.ssm via kernels.ssd_scan.ref)",
)
