"""Kernel micro-bench, driven by the ``repro.ops`` registry.

Instead of hardcoded function calls, the sweep *iterates the registered
backends* for each op — a new backend shows up in the sweep the moment it
is registered — and every record carries the resolved spec, so an emitted
JSON row is a reproducible invocation, not just a number.

Wall-times on CPU are interpret-mode numbers (NOT TPU performance); the
derived column reports the kernel's arithmetic-intensity bookkeeping used
by §Perf.

    PYTHONPATH=src python -m benchmarks.kernel_bench                # all impls
    PYTHONPATH=src python -m benchmarks.kernel_bench --impl pallas  # one impl
    PYTHONPATH=src python -m benchmarks.kernel_bench --json out.json
"""

from __future__ import annotations

import argparse
import json
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from benchmarks._timing import time_device_fn_us as _t
from repro import ops
from repro.core.fixedpoint import DEFAULT_FORMAT


def _record(records, name, us, spec, **derived):
    row = {"name": name, "us": round(us, 1), "spec": ops.spec_json(spec), **derived}
    records.append(row)
    extra = ",".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us:.0f},{extra}" if extra else f"{name},{us:.0f}")


def _valid_spec(spec):
    """True when the selected backend's capability table accepts the spec."""
    try:
        ops.resolve(spec)
        return True
    except ops.OpDispatchError:
        return False


def sweep_softmax(records: List[Dict[str, Any]], impl_filter: Optional[str]) -> None:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 512)) * 4, jnp.float32)
    # STAR op accounting: per element 1 quant + 1 LUT; per row 1 VMM(256) + 1 div
    star_ops = x.size * 2 + x.shape[0] * (DEFAULT_FORMAT.num_levels * 2 + 1)
    for backend in ops.backends("softmax"):
        if impl_filter and backend.impl != impl_filter:
            continue
        kind = "exact" if backend.capabilities.get("kind") == ("exact",) else "star"
        spec = ops.validate(ops.SoftmaxSpec(impl=backend.impl, kind=kind))
        us = _t(lambda: ops.softmax(x, spec))
        derived = {"engine_ops": star_ops} if kind == "star" else {}
        _record(records, f"softmax_{backend.impl}_64x512", us, spec, **derived)


def sweep_attention(records: List[Dict[str, Any]], impl_filter: Optional[str]) -> None:
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    flops = 4 * 256 * 256 * 4 * 64  # QK^T + PV
    for backend in ops.backends("attention"):
        if impl_filter and backend.impl != impl_filter:
            continue
        spec = ops.validate(ops.AttentionSpec(
            impl=backend.impl, causal=True, block_q=64, block_k=64, block_kv=64
        ))
        us = _t(lambda: ops.attention(q, k, v, spec), iters=2)
        _record(records, f"attn_{backend.impl}_256", us, spec, attn_flops=flops)
        if _valid_spec(spec := ops.AttentionSpec(
            impl=backend.impl, causal=True, block_q=64, block_k=64, pv_int8=True
        )):
            us8 = _t(lambda: ops.attention(q, k, v, spec), iters=2)
            _record(
                records, f"attn_{backend.impl}_256_int8pv", us8, spec,
                pv_bytes_saved="0.5x",
            )


def sweep_paged_decode(
    records: List[Dict[str, Any]], impl_filter: Optional[str]
) -> None:
    """Paged decode: pool size x active length, every registered backend.

    Wall-time on CPU is interpret-mode noise; the column that matters is
    ``gather_bytes`` — the counted K+V bytes the backend reads from the
    page pool per decode step (``ops.paged_gather_bytes``).  The gather
    adapters pay the full ``S*W*bs`` table window regardless of occupancy;
    ``pallas_paged`` pays only live pages, so its advantage grows with
    pool/active ratio (the ``bytes_vs_gather`` column and the summary
    speedup rows).
    """
    rng = np.random.default_rng(0)
    s, bs, hq, hkv, d = 4, 16, 4, 2, 64
    ratios: Dict[tuple, Dict[str, int]] = {}
    for w in (4, 16):  # table width -> per-slot pool of w*bs rows
        n = s * w + 1  # + scratch block 0
        q = jnp.asarray(rng.normal(size=(s, 1, hq, d)), jnp.float32)
        # a stack of one layer's pools: [L=1, N, bs, Hkv, D]
        kp = jnp.asarray(rng.normal(size=(1, n, bs, hkv, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(1, n, bs, hkv, d)), jnp.float32)
        tables = jnp.arange(1, s * w + 1, dtype=jnp.int32).reshape(s, w)
        for live in (8, w * bs // 2, w * bs):
            kvl = jnp.full((s,), live, jnp.int32)
            for backend in ops.backends("paged_attention"):
                if impl_filter and backend.impl != impl_filter:
                    continue
                spec = ops.validate(
                    ops.PagedAttentionSpec(impl=backend.impl, block_size=bs)
                )
                us = _t(
                    lambda: ops.paged_attention(
                        q, kp, vp, tables, spec,
                        kv_valid_len=kvl, layer=0, kv_len=w * bs,
                    ),
                    iters=2,
                )
                gb = ops.paged_gather_bytes(
                    backend.impl, table_width=w, block_size=bs,
                    live_lens=[live] * s, num_kv_heads=hkv, head_dim=d,
                )
                ratios.setdefault((w, live), {})[backend.impl] = gb
                _record(
                    records,
                    f"paged_decode_{backend.impl}_pool{w * bs}_live{live}",
                    us, spec, gather_bytes=gb,
                    pool_rows=w * bs, live_rows=live,
                )
    # interpret-normalized speedup: counted pool-read bytes, gather vs
    # gather-free, per (pool, active) point
    for (w, live), by_impl in sorted(ratios.items()):
        if "xla" in by_impl and "pallas_paged" in by_impl:
            ratio = by_impl["xla"] / by_impl["pallas_paged"]
            row = {
                "name": f"paged_decode_bytes_speedup_pool{w * bs}_live{live}",
                "speedup": round(ratio, 2),
                "gather_bytes": by_impl["xla"],
                "pallas_paged_bytes": by_impl["pallas_paged"],
            }
            records.append(row)
            print(f"{row['name']},{ratio:.2f}x,counted_pool_read_bytes")


def sweep_paged_kv_dtype(
    records: List[Dict[str, Any]], impl_filter: Optional[str],
    dtype_filter: Optional[str] = None,
) -> None:
    """Quantized paged decode across ``kv_dtype`` layouts (DESIGN.md §13).

    Fixed at the pool-256 / live-8 acceptance point of the paged sweep:
    the column that matters is ``kv_bytes_per_token`` — counted pool-read
    bytes per decode token (codes + the per-(block, head) scale rows) —
    plus ``pool_bytes``, the whole pool's resident footprint at that
    dtype.  Two invariants are asserted, not just printed: the int8
    layout reads ≤ 0.55x the fp32 bytes/token (the compression target CI
    re-checks from serve_throughput), and the quantized pallas_paged
    jaxpr still never materializes the [S, W*bs, H, D] gathered window.
    """
    from repro.core import kvquant

    rng = np.random.default_rng(0)
    s, w, bs, hq, hkv, d, live = 4, 16, 16, 4, 2, 64, 8
    n = s * w + 1
    q = jnp.asarray(rng.normal(size=(s, 1, hq, d)), jnp.float32)
    # a stack of one layer's pools: [L=1, N, bs, Hkv, D]
    kf = jnp.asarray(rng.normal(size=(1, n, bs, hkv, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(1, n, bs, hkv, d)), jnp.float32)
    tables = jnp.arange(1, s * w + 1, dtype=jnp.int32).reshape(s, w)
    kvl = jnp.full((s,), live, jnp.int32)
    per_tok: Dict[tuple, float] = {}
    for kv_dtype in kvquant.KV_DTYPES:
        if dtype_filter and kv_dtype != dtype_filter:
            continue
        if kv_dtype == "fp32":
            kp, vp, scales = kf, vf, None
        else:
            kp, ks = kvquant.quantize_blocks(kf, kv_dtype)
            vp, vs = kvquant.quantize_blocks(vf, kv_dtype)
            scales = (ks, vs)
        scale_bytes = 2 * 4 * hkv if scales is not None else 0  # k+v, f32
        for backend in ops.backends("paged_attention"):
            if impl_filter and backend.impl != impl_filter:
                continue
            spec = ops.validate(ops.PagedAttentionSpec(
                impl=backend.impl, block_size=bs, kv_dtype=kv_dtype))

            def call():
                return ops.paged_attention(
                    q, kp, vp, tables, spec,
                    kv_valid_len=kvl, layer=0, kv_len=w * bs, kv_scales=scales,
                )

            us = _t(call, iters=2)
            gb = ops.paged_gather_bytes(
                backend.impl, table_width=w, block_size=bs,
                live_lens=[live] * s, num_kv_heads=hkv, head_dim=d,
                dtype_bytes=kp.dtype.itemsize,
                scale_bytes_per_block=scale_bytes,
            )
            bpt = gb / s
            pool_bytes = n * (2 * bs * hkv * d * kp.dtype.itemsize
                              + scale_bytes)
            per_tok[(backend.impl, kv_dtype)] = bpt
            _record(
                records,
                f"paged_decode_{backend.impl}_{kv_dtype}_pool{w * bs}"
                f"_live{live}",
                us, spec, gather_bytes=gb, kv_bytes_per_token=round(bpt, 1),
                pool_bytes=pool_bytes,
            )
            if backend.impl == "pallas_paged" and scales is not None:
                assert not ops.materializes(
                    call, (s, w * bs, hkv, d)
                ), f"{kv_dtype} pallas_paged materialized the gathered window"
    for impl in sorted({i for i, _ in per_tok}):
        f32, i8 = per_tok.get((impl, "fp32")), per_tok.get((impl, "int8"))
        if f32 is None or i8 is None:
            continue
        ratio = i8 / f32
        row = {
            "name": f"paged_decode_kv_compression_{impl}_pool256_live8",
            "int8_vs_fp32_bytes_per_token": round(ratio, 3),
            "fp32_bytes_per_token": round(f32, 1),
            "int8_bytes_per_token": round(i8, 1),
        }
        records.append(row)
        print(f"{row['name']},{ratio:.3f}x,counted_bytes_per_token")
        assert ratio <= 0.55, (
            f"int8 paged reads {ratio:.2f}x the fp32 bytes/token for "
            f"{impl} (compression target: <= 0.55x)"
        )


def sweep_matmul(records: List[Dict[str, Any]], impl_filter: Optional[str]) -> None:
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 256)) * 0.05, jnp.float32)
    for backend in ops.backends("matmul"):
        if impl_filter and backend.impl != impl_filter:
            continue
        spec = ops.validate(ops.MatmulSpec(impl=backend.impl))
        us = _t(lambda: ops.matmul(a, w, spec))
        derived = {}
        if backend.impl == "hwmodel":  # crossbar accounting only where one exists
            xbar = spec.crossbar
            derived["xbar_reads"] = (256 // xbar.tile_rows) * (256 // xbar.tile_cols)
        _record(records, f"matmul_{backend.impl}_64x256x256", us, spec, **derived)


def sweep_ssd_scan(records: List[Dict[str, Any]], impl_filter: Optional[str]) -> None:
    rng = np.random.default_rng(0)
    xdt = jnp.asarray(rng.normal(size=(1, 256, 8, 32)), jnp.float32)
    ad = -jnp.abs(jnp.asarray(rng.normal(size=(1, 256, 8)) * 0.1, jnp.float32))
    bm = jnp.asarray(rng.normal(size=(1, 256, 32)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(1, 256, 32)) * 0.3, jnp.float32)
    for backend in ops.backends("ssd_scan"):
        if impl_filter and backend.impl != impl_filter:
            continue
        spec = ops.validate(ops.ScanSpec(impl=backend.impl, chunk=64))
        us = _t(lambda: ops.ssd_scan(xdt, ad, bm, cm, spec)[0], iters=2)
        _record(
            records, f"ssd_scan_{backend.impl}_256", us, spec,
            vmem_state_bytes=8 * 32 * 32 * 4,
        )


def main(argv: Optional[List[str]] = None) -> bool:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--impl", default=None,
        help="only sweep this registry impl (default: every registered backend)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the records (incl. resolved specs) as JSON",
    )
    ap.add_argument(
        "--only", default=None,
        choices=("softmax", "attention", "paged_decode", "paged_kv_dtype",
                 "ssd_scan", "matmul"),
        help="run a single sweep (e.g. --only paged_decode for the "
        "BENCH_paged_decode.json emission, --only paged_kv_dtype for "
        "BENCH_kv_quant.json)",
    )
    ap.add_argument(
        "--kv-dtype", default=None, choices=("fp32", "int8", "fp8_e4m3"),
        help="restrict the paged_kv_dtype sweep to one KV storage layout "
        "(default: sweep all three)",
    )
    args = ap.parse_args(argv)

    sweeps = {
        "softmax": sweep_softmax,
        "attention": sweep_attention,
        "paged_decode": sweep_paged_decode,
        "paged_kv_dtype": lambda r, i: sweep_paged_kv_dtype(
            r, i, args.kv_dtype),
        "ssd_scan": sweep_ssd_scan,
        "matmul": sweep_matmul,
    }
    records: List[Dict[str, Any]] = []
    for name, fn in sweeps.items():
        if args.only is None or args.only == name:
            fn(records, args.impl)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=2)
        print(f"# wrote {len(records)} records to {args.json}")
    return True


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
