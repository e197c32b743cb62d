"""Paper §II precision analysis: softmax is precision-insensitive — a 7-9
bit fixed-point LUT preserves model accuracy (CNEWS 8b / MRPC 9b / CoLA 7b).

Protocol (the paper's, at laptop scale): train a small bidirectional
attention classifier with EXACT softmax on an attention-critical retrieval
task (induction: find the repeat of the cue token, report its successor),
then swap the attention softmax for the STAR engine at decreasing bitwidths.
The claim reproduces as: accuracy(calibrated 7-9 bit) ~ accuracy(exact),
collapsing at very low bitwidths where attention can no longer stay sharp.

The fault sweep (DESIGN.md §9) extends the same protocol past quantization:
for each calibrated format it scans stuck-cell rate x conductance sigma
(seeded :class:`~repro.ops.FaultModel` realizations on the same trained
model) and emits accuracy-vs-fault curves — ``--json`` writes them next to
the bitwidth results::

    python benchmarks/accuracy_bitwidth.py --json out.json \
        --fault-sigma 0,0.1,0.3 --fault-stuck-rate 0,0.02,0.1
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops
from repro.core.fixedpoint import FixedPointFormat

D, H, LAYERS, VOCAB, CLASSES, SEQ = 64, 4, 2, 32, 8, 32


def gen_data(n, seed):
    """Induction retrieval: toks[0] is a cue; it reappears once at a random
    position p; the label is toks[p+1] % CLASSES."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(CLASSES, VOCAB, (n, SEQ)).astype(np.int32)  # filler
    cue = rng.integers(CLASSES, VOCAB, n)
    p = rng.integers(2, SEQ - 1, n)
    ans = rng.integers(0, CLASSES, n)
    rows = np.arange(n)
    toks[rows, 0] = cue
    toks[rows, p] = cue
    toks[rows, p + 1] = ans  # answer tokens live in [0, CLASSES)
    return jnp.asarray(toks), jnp.asarray(ans)


def init_params(key):
    ks = jax.random.split(key, 3 + LAYERS)
    p = {
        "emb": jax.random.normal(ks[0], (VOCAB, D)) * 0.1,
        "pos": jax.random.normal(ks[1], (SEQ, D)) * 0.1,
        "head": jax.random.normal(ks[2], (D, CLASSES)) * 0.1,
        "layers": [],
    }
    for i in range(LAYERS):
        k1, k2, k3, k4, k5, k6 = jax.random.split(ks[3 + i], 6)
        p["layers"].append({
            "wq": jax.random.normal(k1, (D, D)) * D ** -0.5,
            "wk": jax.random.normal(k2, (D, D)) * D ** -0.5,
            "wv": jax.random.normal(k3, (D, D)) * D ** -0.5,
            "wo": jax.random.normal(k4, (D, D)) * D ** -0.5,
            "w1": jax.random.normal(k5, (D, 2 * D)) * D ** -0.5,
            "w2": jax.random.normal(k6, (2 * D, D)) * (2 * D) ** -0.5,
        })
    return p


def _norm(x):
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) / jnp.sqrt(D) + 1e-6)


def forward(p, toks, softmax: ops.SoftmaxSpec):
    spec = ops.AttentionSpec(impl="reference", softmax=softmax)  # bidirectional
    x = p["emb"][toks] + p["pos"][None]
    for lp in p["layers"]:
        xn = _norm(x)
        q = (xn @ lp["wq"]).reshape(*xn.shape[:2], H, D // H)
        k = (xn @ lp["wk"]).reshape(*xn.shape[:2], H, D // H)
        v = (xn @ lp["wv"]).reshape(*xn.shape[:2], H, D // H)
        a = ops.attention(q, k, v, spec)
        x = x + a.reshape(xn.shape) @ lp["wo"]
        x = x + jax.nn.gelu(_norm(x) @ lp["w1"]) @ lp["w2"]
    return x[:, 0] @ p["head"]  # classify from the cue position


def train(steps=400, lr=2e-3, seed=0):
    key = jax.random.PRNGKey(seed)
    p = init_params(key)
    exact = ops.SoftmaxSpec(kind="exact")
    mom = jax.tree.map(jnp.zeros_like, p)
    vel = jax.tree.map(jnp.zeros_like, p)

    def loss_fn(p, toks, cls):
        logits = forward(p, toks, exact)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(len(cls)), cls])

    @jax.jit
    def step(p, mom, vel, toks, cls, t):
        l, g = jax.value_and_grad(loss_fn)(p, toks, cls)
        mom = jax.tree.map(lambda m, gw: 0.9 * m + 0.1 * gw, mom, g)
        vel = jax.tree.map(lambda v, gw: 0.99 * v + 0.01 * gw * gw, vel, g)
        c1 = 1 - 0.9 ** t
        c2 = 1 - 0.99 ** t
        p = jax.tree.map(
            lambda w, m, v: w - lr * (m / c1) / (jnp.sqrt(v / c2) + 1e-8),
            p, mom, vel,
        )
        return p, mom, vel, l

    for s in range(steps):
        toks, cls = gen_data(128, seed=1000 + s)
        p, mom, vel, l = step(p, mom, vel, toks, cls, jnp.asarray(s + 1.0))
    return p


def evaluate(p, softmax: ops.SoftmaxSpec, seed=9) -> float:
    toks, cls = gen_data(1024, seed)
    pred = jnp.argmax(forward(p, toks, softmax), -1)
    return float(jnp.mean(pred == cls))


SWEEPS = [
    ("mrpc_9b", FixedPointFormat(6, 3)),
    ("cnews_8b", FixedPointFormat(6, 2)),
    ("cola_7b", FixedPointFormat(5, 2)),
    ("6b", FixedPointFormat(5, 1)),
    ("5b", FixedPointFormat(4, 1)),
    ("4b", FixedPointFormat(3, 1)),
    ("3b", FixedPointFormat(2, 1)),
    ("2b", FixedPointFormat(1, 1)),
]

# calibrated formats the fault sweep stresses (>= 2, per the paper's own
# per-dataset calibration points)
FAULT_FORMATS = [
    ("cnews_8b", FixedPointFormat(6, 2)),
    ("cola_7b", FixedPointFormat(5, 2)),
]


def run(steps: int = 400) -> Tuple[Dict[str, float], dict]:
    p = train(steps=steps)
    results = {"exact": evaluate(p, ops.SoftmaxSpec(kind="exact"))}
    for name, fmt in SWEEPS:
        results[name] = evaluate(p, ops.SoftmaxSpec(kind="star", precision=fmt))
    return results, p


def fault_sweep(
    p,
    sigmas: Sequence[float],
    stuck_rates: Sequence[float],
    seed: int = 0,
) -> List[dict]:
    """Accuracy over the fault grid (stuck rate x sigma) per format.

    Stuck cells split evenly between G_on and G_off; each grid point is one
    seeded realization, so re-runs reproduce the same curve exactly.
    """
    curves: List[dict] = []
    for name, fmt in FAULT_FORMATS:
        for sigma in sigmas:
            for rate in stuck_rates:
                fault = ops.FaultModel(
                    g_sigma=sigma,
                    stuck_on_rate=rate / 2,
                    stuck_off_rate=rate / 2,
                    seed=seed,
                )
                spec = ops.SoftmaxSpec(kind="star", precision=fmt, fault=fault)
                curves.append({
                    "format": name,
                    "g_sigma": sigma,
                    "stuck_rate": rate,
                    "accuracy": evaluate(p, spec),
                    "spec": ops.spec_json(spec),
                })
    return curves


def _float_list(text: str) -> List[float]:
    return [float(v) for v in text.split(",") if v.strip()]


def main(argv: Sequence[str] | None = None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write bitwidth results + fault curves as JSON")
    ap.add_argument("--fault-sigma", type=_float_list, default=[0.0, 0.1, 0.3],
                    metavar="S0,S1,...",
                    help="lognormal conductance sigmas for the fault sweep")
    ap.add_argument("--fault-stuck-rate", type=_float_list,
                    default=[0.0, 0.02, 0.1], metavar="R0,R1,...",
                    help="total stuck-cell rates (split evenly on/off)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="FaultModel realization seed")
    ap.add_argument("--steps", type=int, default=400,
                    help="training steps before the sweeps")
    args = ap.parse_args(argv)

    r, p = run(steps=args.steps)
    for k, v in r.items():
        print(f"accuracy_bitwidth_{k},{v*100:.1f},acc_pct")

    curves = None
    if args.json:
        curves = fault_sweep(
            p, args.fault_sigma, args.fault_stuck_rate, seed=args.fault_seed
        )
        for c in curves:
            print(
                f"accuracy_fault_{c['format']}_s{c['g_sigma']}_r"
                f"{c['stuck_rate']},{c['accuracy']*100:.1f},acc_pct"
            )
        with open(args.json, "w") as f:
            json.dump({"bitwidth": r, "fault_curves": curves}, f, indent=2)
        print(f"wrote {args.json}")

    assert r["exact"] > 0.9, f"training failed to learn the task: {r['exact']}"
    # the paper's claim: calibrated 7-9 bit formats preserve accuracy
    for k in ("cola_7b", "cnews_8b", "mrpc_9b"):
        assert r[k] >= r["exact"] - 0.02, (k, r[k], r["exact"])
    # and extreme truncation eventually hurts
    assert r["2b"] < r["exact"] - 0.02, ("2-bit should degrade", r["2b"])
    return r if curves is None else (r, curves)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
