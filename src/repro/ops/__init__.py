"""``repro.ops`` — the unified op dispatch layer (DESIGN.md §7).

One softmax *contract*, many implementations: frozen hashable specs
(:class:`SoftmaxSpec` / :class:`AttentionSpec` / :class:`MatmulSpec` /
:class:`ScanSpec`) describe an invocation; a capability-checked registry
maps ``(op, impl)`` to a backend; :func:`softmax` / :func:`attention` /
:func:`matmul` / :func:`ssd_scan` dispatch through it.

    from repro import ops

    probs = ops.softmax(x, ops.SoftmaxSpec(precision="auto:mrpc"))
    out = ops.attention(q, k, v, impl="pallas", causal=True)
    with ops.use(softmax="reference", interpret=True):
        ...  # retarget every dispatch in the block (tests / benchmarks)

New backends, precision policies, and hardware targets are registry
entries (:func:`register`), not cross-cutting edits.
"""

from repro.ops.dispatch import (  # noqa: F401
    DEFAULT_ATTENTION,
    DEFAULT_MATMUL,
    DEFAULT_PAGED_ATTENTION,
    DEFAULT_SOFTMAX,
    DEFAULT_SSD_SCAN,
    attention,
    matmul,
    paged_attention,
    resolve,
    softmax,
    ssd_scan,
    validate,
)
from repro.hwmodel.faults import FaultModel  # noqa: F401
from repro.ops.guard import (  # noqa: F401
    AccuracyGuard,
    GuardConfig,
    GuardTripWarning,
)
from repro.ops.platform import (  # noqa: F401
    default_interpret,
    detected_platform,
    resolve_interpret,
)
from repro.ops.registry import (  # noqa: F401
    Backend,
    CapabilityError,
    OpDispatchError,
    UnknownBackendError,
    backends,
    get,
    register,
    registered_ops,
    unregister,
    use,
)
from repro.ops.specs import (  # noqa: F401
    AttentionSpec,
    MatmulSpec,
    PagedAttentionSpec,
    ScanSpec,
    SoftmaxSpec,
    Spec,
    resolve_precision,
    spec_json,
)

# Importing the built-in backends populates the registry as a side effect.
from repro.ops import impls as _impls  # noqa: E402,F401  isort: skip
from repro.ops.impls import materializes, paged_gather_bytes  # noqa: E402,F401  isort: skip
