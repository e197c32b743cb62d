"""Paper §I claim: softmax execution share grows with sequence length and
exceeds the matmul share (59.2% of BERT-base time at seq 512 on GPU).

We time exact softmax vs the attention matmuls on this host (CPU XLA — the
absolute share differs from a GPU, the *trend* is the claim), and report the
STAR engine's op-count view: with the counter+VMM trick a softmax row costs
d CAM searches + 1 VMM + 1 divide instead of d exps + a d-sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._timing import time_device_fn
from repro.hwmodel import constants as C
from repro.hwmodel.star_engine import system_efficiency


def _time(f, *args, iters=5):
    return time_device_fn(lambda: f(*args), iters=iters)


def run(seqs=(128, 256, 512)) -> list:
    d, h = C.BERT_D_MODEL, C.BERT_HEADS
    rows = []
    rng = np.random.default_rng(0)
    for s in seqs:
        q = jnp.asarray(rng.normal(size=(1, h, s, d // h)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, h, s, d // h)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, h, s, d // h)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(1, s, d)), jnp.float32)
        wq = jnp.asarray(rng.normal(size=(d, d)) * 0.02, jnp.float32)

        mm = jax.jit(lambda q, k, v, x, wq: (
            jnp.einsum("bhqd,bhkd->bhqk", q, k),
            x @ wq, x @ wq, x @ wq, x @ wq,  # QKVO projections
        ))
        sm = jax.jit(lambda scores: jax.nn.softmax(scores, axis=-1))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        pv = jax.jit(lambda p, v: jnp.einsum("bhqk,bhkd->bhqd", p, v))

        t_mm = _time(mm, q, k, v, x, wq) + _time(pv, jax.nn.softmax(scores), v)
        t_sm = _time(sm, scores)
        frac = t_sm / (t_sm + t_mm)
        # the hwmodel's accelerator-side share (operand-granularity engine)
        hw = system_efficiency(s, softmax_on_rram=False, vector_pipeline=False)
        rows.append({
            "seq": s,
            "host_softmax_ms": t_sm * 1e3,
            "host_matmul_ms": t_mm * 1e3,
            "host_softmax_share": frac,
            "accel_model_softmax_share": hw["softmax_share"],
        })
    return rows


def main():
    rows = run()
    shares = [r["host_softmax_share"] for r in rows]
    model_shares = [r["accel_model_softmax_share"] for r in rows]
    for r in rows:
        print(f"softmax_fraction_seq{r['seq']},{r['host_softmax_ms']*1e3:.1f},"
              f"host_share={r['host_softmax_share']:.3f},"
              f"accel_model_share={r['accel_model_softmax_share']:.3f}")
    assert shares[-1] > shares[0], "softmax share must grow with seq length"
    assert model_shares[-1] > model_shares[0]
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
