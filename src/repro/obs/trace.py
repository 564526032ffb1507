"""Host-side spans + trace-time annotations for the solver stack.

Two complementary instruments:

* :func:`span` — a host-side timed span. Spans nest into a tree (plan
  build > preconditioner resolve > core pinning; solve > args, dispatch)
  and each span also opens a ``jax.profiler.TraceAnnotation`` so the
  same region shows up in XLA/perfetto profiles under the same name, on
  the clock of the device planes. Spans record while :func:`tracing` is
  true: observability is enabled, or a profiler session is capturing.
  Otherwise a span costs that one check.
* :func:`trace_scope` — a *trace-time* annotation for code that runs
  under ``jit``/``shard_map``. It is ``jax.named_scope``, always on: it
  tags the emitted HLO name stack (the op metadata a profile shows) and
  **adds zero primitives** to the jaxpr — the solver while-loop body is
  byte-identical with observability on or off (asserted in tests via the
  jaxpr census).

Host spans measure wall time with ``time.perf_counter`` around *host*
work (trace, dispatch); JAX dispatch is async, so a span around a solve
measures end-to-end only if the caller synchronizes — ``SolverPlan.solve``
does exactly that when observability is enabled (and not otherwise, so a
running profiler never changes how the program dispatches).

State is process-local and thread-safe: each thread keeps its own open
span stack; finished root spans go into one shared buffer of the latest
``MAX_ROOTS``, read by ``span_tree()`` / ``dump_spans()``. Every span has
an ``id`` and its parent's ``parent_id``; ``call_id`` is the id of its
root, shared by all spans of one call.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax

__all__ = [
    "enable",
    "disable",
    "enabled",
    "tracing",
    "span",
    "trace_scope",
    "Span",
    "MAX_ROOTS",
    "span_tree",
    "clear_spans",
    "spans_to_dicts",
    "dump_spans",
]

# finished root spans kept; a long-lived server with obs on keeps the latest
MAX_ROOTS = 8192

_ENABLED = False
_LOCK = threading.Lock()
_ROOTS: Deque["Span"] = deque(maxlen=MAX_ROOTS)
_IDS = itertools.count(1)
_TLS = threading.local()

try:  # whether an XLA profiler session is capturing
    from jax._src.lib import _profiler as _xla_profiler

    _capturing = _xla_profiler.TraceMe.is_enabled
except (ImportError, AttributeError):  # a build without the profiler
    def _capturing() -> bool:
        return False


def enable() -> None:
    """Turn observability on process-wide (spans record, metrics count)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    """Turn observability off; metrics revert to no-ops, spans to profiler-only."""
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def tracing() -> bool:
    """Whether spans record: observability is enabled or a profiler is capturing."""
    return _ENABLED or _capturing()


@dataclass
class Span:
    """One timed region; children are spans opened while it was open."""

    name: str
    attrs: Dict[str, Any] = field(default_factory=dict)
    t_start: float = 0.0
    t_end: float = 0.0
    children: List["Span"] = field(default_factory=list)
    id: int = 0
    parent_id: Optional[int] = None
    call_id: int = 0

    @property
    def duration_s(self) -> float:
        return max(self.t_end - self.t_start, 0.0)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "id": self.id,
            "parent_id": self.parent_id,
            "call_id": self.call_id,
            "duration_s": self.duration_s,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def find(self, name: str) -> Optional["Span"]:
        """First descendant (or self) with this name, depth-first."""
        if self.name == name:
            return self
        for c in self.children:
            hit = c.find(name)
            if hit is not None:
                return hit
        return None


def _stack() -> List[Span]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


@contextlib.contextmanager
def span(name: str, **attrs):
    """Open a named host span (and an XLA TraceAnnotation) around a block.

    Yields the :class:`Span` (or None when not :func:`tracing`) so callers
    can attach attributes discovered mid-block: ``sp and sp.attrs.update(...)``.
    """
    if not tracing():
        yield None
        return
    st = _stack()
    parent = st[-1] if st else None
    sp = Span(name=name, attrs=dict(attrs), id=next(_IDS))
    if parent is None:
        sp.call_id = sp.id
    else:
        sp.parent_id, sp.call_id = parent.id, parent.call_id
    st.append(sp)
    ann = _trace_annotation(name)
    sp.t_start = time.perf_counter()
    try:
        with ann:
            yield sp
    finally:
        sp.t_end = time.perf_counter()
        st.pop()
        if parent is not None:
            parent.children.append(sp)
        else:
            with _LOCK:
                _ROOTS.append(sp)


def _trace_annotation(name: str):
    # lazy + defensive: profiler availability varies across backends and
    # headless builds; host spans must never fail because of it
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


def trace_scope(name: str):
    """``jax.named_scope(name)``, whether or not observability is enabled.

    Safe inside jitted/shard_mapped code: named_scope annotates the HLO
    name stack at trace time and emits no primitives, so the compiled
    program computes the same either way — its ops just carry the layer's
    name in their metadata (iteration / reduce / spmv phases, the pad).
    """
    return jax.named_scope(name)


def span_tree() -> Tuple[Span, ...]:
    """The latest ``MAX_ROOTS`` finished root spans, oldest first."""
    with _LOCK:
        return tuple(_ROOTS)


def clear_spans() -> None:
    with _LOCK:
        _ROOTS.clear()


def spans_to_dicts() -> List[dict]:
    return [s.to_dict() for s in span_tree()]


def dump_spans(path: str) -> None:
    """Write the span tree as JSON (one object, ``{"spans": [...]}``)."""
    with open(path, "w") as f:
        json.dump({"spans": spans_to_dicts()}, f, indent=2)
