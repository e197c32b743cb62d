"""Capability-checked backend registry for the ``repro.ops`` dispatch layer.

Backends register under ``(op, impl)`` keys with a declarative capability
table: a mapping from spec field path (dotted paths reach nested specs,
e.g. ``"softmax.kind"``) to the tuple of values the backend supports.
Dispatch validates the spec against the table before calling the backend,
so a mismatch fails with an actionable error — which field, what the
backend supports, and which registered impls *do* support the request —
instead of a shape error three layers down.

``use(...)`` pushes a context-local override frame: tests and benchmarks
can retarget every dispatch (``use(softmax="reference")``, or
``use(interpret=True)``) without threading kwargs through call sites.
"""

from __future__ import annotations

import contextlib
import dataclasses
from contextvars import ContextVar
from typing import Any, Callable, Dict, Iterator, Mapping, Optional, Tuple


class OpDispatchError(ValueError):
    """Base class for dispatch-layer errors."""


class UnknownBackendError(OpDispatchError):
    """No backend registered under the requested (op, impl)."""


class CapabilityError(OpDispatchError):
    """The selected backend cannot execute the requested spec."""


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered implementation of an op.

    ``fn(spec, *args, **kwargs)`` receives the fully-resolved spec (impl
    overrides applied, ``interpret`` concrete) plus the runtime arrays.
    ``capabilities`` maps spec field paths to allowed value tuples; fields
    not listed are unconstrained.
    """

    op: str
    impl: str
    fn: Callable[..., Any]
    capabilities: Mapping[str, Tuple[Any, ...]] = dataclasses.field(
        default_factory=dict
    )
    description: str = ""


_REGISTRY: Dict[Tuple[str, str], Backend] = {}


def register(
    op: str,
    impl: str,
    fn: Callable[..., Any],
    *,
    capabilities: Optional[Mapping[str, Tuple[Any, ...]]] = None,
    description: str = "",
    overwrite: bool = False,
) -> Backend:
    """Register (or with ``overwrite=True`` replace) a backend."""
    key = (op, impl)
    if key in _REGISTRY and not overwrite:
        raise OpDispatchError(
            f"backend {impl!r} already registered for op {op!r}; "
            f"pass overwrite=True to replace it"
        )
    backend = Backend(op, impl, fn, dict(capabilities or {}), description)
    _REGISTRY[key] = backend
    return backend


def unregister(op: str, impl: str) -> None:
    _REGISTRY.pop((op, impl), None)


def get(op: str, impl: str) -> Backend:
    backend = _REGISTRY.get((op, impl))
    if backend is None:
        known = sorted(b.impl for b in backends(op))
        if not known:
            raise UnknownBackendError(
                f"no backends registered for op {op!r} "
                f"(is repro.ops.impls imported?)"
            )
        raise UnknownBackendError(
            f"no {op!r} backend named {impl!r}; registered impls: {known}"
        )
    return backend


def backends(op: str) -> Tuple[Backend, ...]:
    """All registered backends for an op, sorted by impl name."""
    found = [b for (o, _), b in _REGISTRY.items() if o == op]
    return tuple(sorted(found, key=lambda b: b.impl))


def registered_ops() -> Tuple[str, ...]:
    """All op names with at least one registered backend."""
    return tuple(sorted({o for (o, _) in _REGISTRY}))


def _field_value(spec: Any, path: str) -> Any:
    value = spec
    for part in path.split("."):
        value = getattr(value, part)
    return value


def validate(backend: Backend, spec: Any) -> None:
    """Raise :class:`CapabilityError` unless ``backend`` can execute ``spec``."""
    for path, allowed in backend.capabilities.items():
        value = _field_value(spec, path)
        if value not in allowed:
            others = [
                b.impl
                for b in backends(backend.op)
                if b.impl != backend.impl
                and _field_value(spec, path) in b.capabilities.get(path, (value,))
            ]
            hint = (
                f"; impls supporting {path}={value!r}: {sorted(others)}"
                if others
                else ""
            )
            raise CapabilityError(
                f"{backend.op} backend {backend.impl!r} does not support "
                f"{path}={value!r} (supported: {list(allowed)}){hint}"
            )


# --- context-local overrides (ops.use) -------------------------------------

_OVERRIDE_FRAMES: ContextVar[Tuple[Mapping[str, Any], ...]] = ContextVar(
    "repro_ops_overrides", default=()
)

_OVERRIDE_KEYS = (
    "softmax",
    "attention",
    "paged_attention",
    "matmul",
    "ssd_scan",
    "interpret",
)


@contextlib.contextmanager
def use(**overrides: Any) -> Iterator[None]:
    """Context manager retargeting dispatch inside the ``with`` block.

    Keys are op names (value: impl name to force) or ``interpret`` (value:
    bool forced onto every spec).  Inner frames win over outer frames; both
    win over the spec's own ``impl``/``interpret`` — that is the point:
    tests and benchmarks can re-route code that pinned a backend.

        with ops.use(softmax="reference", interpret=True):
            ...  # every softmax dispatch runs the pure-jnp engine

    Overrides resolve at *trace* time: enter the context before jitting
    (or tracing) the function you want retargeted — a function traced
    outside the block keeps the backend it was traced with.
    """
    bad = sorted(set(overrides) - set(_OVERRIDE_KEYS))
    if bad:
        raise OpDispatchError(
            f"unknown ops.use() keys {bad}; valid keys: {list(_OVERRIDE_KEYS)}"
        )
    token = _OVERRIDE_FRAMES.set(_OVERRIDE_FRAMES.get() + (dict(overrides),))
    try:
        yield
    finally:
        _OVERRIDE_FRAMES.reset(token)


def override_key() -> Tuple[Tuple[Tuple[str, Any], ...], ...]:
    """The override stack as a hashable key: a jit cache keyed by it traces
    anew under other overrides, as the overrides resolve at trace time."""
    return tuple(tuple(sorted(f.items())) for f in _OVERRIDE_FRAMES.get())


def active_overrides(op: str) -> Dict[str, Any]:
    """Collapse the override stack for one op: {'impl': ..., 'interpret': ...}."""
    out: Dict[str, Any] = {}
    for frame in _OVERRIDE_FRAMES.get():
        if op in frame:
            out["impl"] = frame[op]
        if "interpret" in frame:
            out["interpret"] = frame["interpret"]
    return out
