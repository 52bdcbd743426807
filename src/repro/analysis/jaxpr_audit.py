"""Layer 2 — trace the registered hot paths and audit their compiled form.

The AST linter (Layer 1) reasons about *source*; this layer reasons about
what jax actually *stages*.  For every :class:`~repro.analysis.hotpaths.HotPathSpec`
it:

1. **registry cross-check** — building the spec imports the defining module;
   the spec's ``registry_name`` must then appear in the ``@compiled_path``
   registry (a spec drifting away from production marking is itself a
   finding);
2. **jaxpr callback scan** — traces the raw callable per shape bucket and
   recursively walks every equation (including sub-jaxprs: ``scan``,
   ``cond``, ``while``, ``pjit``, custom-vjp closures) asserting zero host
   callback primitives (``pure_callback``, ``io_callback``,
   ``debug_callback``, infeed/outfeed);
3. **lowered-module transfer scan** — lowers per bucket and greps the
   StableHLO text for host-transfer ops (``stablehlo.send/recv/infeed/
   outfeed``, XLA python callback custom-calls);
4. **retrace audit** — wraps the callable with a trace counter, jits it
   ONCE, calls it twice per declared bucket, and asserts exactly one trace
   per bucket: shapes inside a bucket are fixed and nothing value-dependent
   forces a retrace (the recompile-hazard invariant, proven rather than
   linted).

Everything here is static — tracing and lowering only; the audit never
executes a compiled step.  jax is imported lazily so ``repro.analysis``
stays importable without it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Sequence

from .hotpaths import HotPathSpec, hot_path_specs

__all__ = ["PathAudit", "audit_path", "audit_hot_paths", "scan_jaxpr_callbacks"]

# Primitive names that move work or data to the host mid-program.  Matched by
# substring ("callback" catches pure_callback / io_callback / debug_callback
# and the xla_python_*_callback forms some jax versions surface directly).
_CALLBACK_SUBSTRINGS = ("callback",)
_CALLBACK_EXACT = frozenset({"infeed", "outfeed"})

# Host-transfer patterns in lowered StableHLO text.
_HLO_TRANSFER_RE = re.compile(
    r"stablehlo\.(send|recv|infeed|outfeed)\b"
    r"|xla_python_(cpu|gpu)_callback"
    r"|host_callback"
    r"|PythonCallback",
)


@dataclasses.dataclass
class PathAudit:
    """Machine-readable audit verdict for one hot path (one ANALYSIS.json
    entry)."""

    name: str
    registry_name: str
    description: str
    buckets: list
    registered: bool = False
    kind: Optional[str] = None
    callback_prims: list = dataclasses.field(default_factory=list)
    transfer_ops: list = dataclasses.field(default_factory=list)
    traces: int = -1
    expected_traces: int = -1
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (
            self.error is None
            and self.registered
            and not self.callback_prims
            and not self.transfer_ops
            and self.traces == self.expected_traces
        )

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        return d


def _is_callback_prim(name: str) -> bool:
    if name in _CALLBACK_EXACT:
        return True
    return any(s in name for s in _CALLBACK_SUBSTRINGS)


def scan_jaxpr_callbacks(jaxpr) -> list[str]:
    """All host-callback primitive names in ``jaxpr``, recursively (scan /
    cond / while / pjit bodies included).  Order: first occurrence."""
    found: list[str] = []
    seen: set[int] = set()

    def walk(jx):
        if id(jx) in seen:  # closed-over jaxprs can alias
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if _is_callback_prim(name) and name not in found:
                found.append(name)
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


def _sub_jaxprs(param):
    """Yield any jaxprs nested inside an eqn param (ClosedJaxpr, Jaxpr, or
    (possibly nested) tuples/lists of them)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(param, ClosedJaxpr):
        yield param.jaxpr
    elif isinstance(param, Jaxpr):
        yield param
    elif isinstance(param, (tuple, list)):
        for p in param:
            yield from _sub_jaxprs(p)


def _scan_lowered_text(fn, args) -> list[str]:
    """Host-transfer op names in the lowered StableHLO module for one
    bucket."""
    import jax

    # Audit tooling: lowers once per bucket by design, never on a hot path.
    text = jax.jit(fn).lower(*args).as_text()  # repro-lint: disable=JS201
    return sorted({m.group(0) for m in _HLO_TRANSFER_RE.finditer(text)})


def audit_path(spec: HotPathSpec) -> PathAudit:
    """Run the full four-part audit for one spec; never raises — failures
    come back as a non-``ok`` :class:`PathAudit`."""
    audit = PathAudit(
        name=spec.name,
        registry_name=spec.registry_name,
        description=spec.description,
        buckets=[],
    )
    try:
        import jax

        fn, buckets = spec.build()
        audit.buckets = [label for label, _ in buckets]

        from .registry import registered_paths

        info = registered_paths().get(spec.registry_name)
        audit.registered = info is not None
        audit.kind = info.kind if info else None

        for label, args in buckets:
            jaxpr = jax.make_jaxpr(fn)(*args)
            for prim in scan_jaxpr_callbacks(jaxpr):
                entry = f"{label}:{prim}"
                if entry not in audit.callback_prims:
                    audit.callback_prims.append(entry)
            for op in _scan_lowered_text(fn, args):
                entry = f"{label}:{op}"
                if entry not in audit.transfer_ops:
                    audit.transfer_ops.append(entry)

        # Retrace audit: ONE jitted object, two calls per bucket, exactly
        # one trace per declared bucket.
        count = {"n": 0}

        def counting(*a):
            count["n"] += 1
            return fn(*a)

        jitted = jax.jit(counting)  # repro-lint: disable=JS201 (one-shot audit jit)
        for _label, args in buckets:
            jax.block_until_ready(jitted(*args))
            jax.block_until_ready(jitted(*args))
        audit.traces = count["n"]
        audit.expected_traces = len(buckets)
    except Exception as e:  # pragma: no cover - exercised via broken specs
        audit.error = f"{type(e).__name__}: {e}"
    return audit


def audit_hot_paths(specs: Optional[Sequence[HotPathSpec]] = None) -> list[PathAudit]:
    """Audit every registered hot path (default: :func:`hot_path_specs`)."""
    return [audit_path(s) for s in (specs if specs is not None else hot_path_specs())]
