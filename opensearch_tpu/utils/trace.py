"""Request tracing: nested spans with a ring buffer of finished traces,
on the device profiler's clock.

Reference analog: `telemetry/tracing/Tracer.java` (+ the telemetry-otel
plugin). Spans carry name/attributes/duration and parent links via a
contextvar, so instrumented layers (REST facade, coordinator, per-shard
query phase, serving ladder, reduce, fetch) nest naturally without
passing a context object around. No exporter: completed root spans land
in a bounded in-memory ring the stats API serves — the deterministic,
dependency-free equivalent of an OTel in-memory span processor.

Every span is also a `jax.profiler.TraceAnnotation("ostpu:" + name)` for
its lifetime: whenever a profiler session is running, the program's
spans are events on the `/host:CPU` plane of the same `.xplane.pb` that
holds the device's `XLA Ops`, so a device idle gap can be laid over the
host layer that was running (`benchmark/span_reduce.py`). There is no
switch: "tracing off" is "no profiler session", and outside a session a
span makes one flag check (`TraceAnnotation.is_enabled()`) and builds no
annotation. `jax` is imported at the first span,
never at import of this module (no backend is initialised from here).

Thread-safety contract: spans may START on pool threads (the
context-carrying submit in `utils/threadpool.py` propagates the ambient
parent into workers), so `parent.children.append` happens concurrently —
child attachment is lock-guarded. Cross-process traces (cluster/distnode)
graft serialized remote subtrees via `attach_remote`, keyed to the wire
context from `wire_context()`."""

from __future__ import annotations

import contextvars
import functools
import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

PROFILER_PREFIX = "ostpu:"

_current: contextvars.ContextVar = contextvars.ContextVar(
    "opensearch_tpu_span", default=None)

# one lock for all child/remote attachment: attachment is rare relative to
# span bodies and a per-span lock would cost a slot on every span
_attach_lock = threading.Lock()

_annotation_cls = None


def _profiler_annotation():
    """`jax.profiler.TraceAnnotation`, imported at the first span."""
    global _annotation_cls
    from jax.profiler import TraceAnnotation
    _annotation_cls = TraceAnnotation
    return TraceAnnotation


class Span:
    """One span; its own context manager (`with TRACER.span(...) as s`).
    `start_ns` / `end_ns` are `time.perf_counter_ns()`; `trace_id` is the
    root's `span_id`, shared by every span of one request."""

    __slots__ = ("span_id", "trace_id", "name", "attributes", "start_ns",
                 "end_ns", "children", "parent", "remote_children",
                 "_tracer", "_token", "_annotation")

    def __init__(self, tracer: "Tracer", span_id: int, name: str,
                 attributes: dict):
        self._tracer = tracer
        self.span_id = span_id
        self.trace_id = span_id
        self.name = name
        self.attributes = attributes
        self.start_ns = 0
        self.end_ns: Optional[int] = None
        # both stay the shared empty tuple until the first attachment:
        # most spans are leaves, and every list a ring-held span keeps is
        # one more object the collector walks
        self.children: Sequence["Span"] = ()
        # pre-serialized subtrees grafted from other processes (distnode
        # RPC responses carry the remote node's span tree)
        self.remote_children: Sequence[dict] = ()
        self.parent: Optional["Span"] = None
        self._annotation = None

    def __enter__(self) -> "Span":
        parent = self.parent = _current.get()
        if parent is not None:
            self.trace_id = parent.trace_id
            # pool threads share a parent (context-carrying submit):
            # concurrent appends must not lose children
            with _attach_lock:
                if parent.children:
                    parent.children.append(self)
                else:
                    parent.children = [self]
        self._token = _current.set(self)
        cls = _annotation_cls or _profiler_annotation()
        if cls.is_enabled():        # a profiler session is recording
            self._annotation = cls(PROFILER_PREFIX + self.name,
                                   **self.attributes)
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        _current.reset(self._token)
        self._token = None          # nothing for the ring to hold
        if self.parent is None:
            self._tracer._finish_root(self)
        return False

    def duration_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None \
            else time.perf_counter_ns()
        return end - self.start_ns

    def self_ns(self, kids: Optional[Sequence["Span"]] = None) -> int:
        """Duration minus what the (local) children cover. Children of
        one thread do not overlap; pool-thread children may, and then
        this is a lower bound clipped at 0."""
        if kids is None:
            with _attach_lock:
                kids = list(self.children)
        return max(self.duration_ns()
                   - sum(c.duration_ns() for c in kids), 0)

    def to_dict(self) -> dict:
        with _attach_lock:
            kids = list(self.children)
            remote = list(self.remote_children)
        children = [c.to_dict() for c in kids] + remote
        return {"name": self.name, "span_id": self.span_id,
                "trace_id": self.trace_id,
                "duration_ms": round(self.duration_ns() / 1e6, 3),
                "self_ms": round(self.self_ns(kids) / 1e6, 3),
                **({"attributes": self.attributes} if self.attributes else {}),
                **({"children": children} if children else {})}


class _NoSpan:
    """`with tracer.span(...) as s` of a disabled tracer: `s` is None and
    no `Span` is built."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class Tracer:
    def __init__(self, max_traces: int = 256, enabled: bool = True):
        self.enabled = enabled
        self._ids = itertools.count(1)
        self._traces: deque = deque(maxlen=max_traces)
        self._lock = threading.Lock()
        self._peeks = 0

    def span(self, name: str, **attributes):
        if not self.enabled:
            return _NO_SPAN
        return Span(self, next(self._ids), name, attributes)

    def spanned(self, name: str):
        """Decorator: every call of the function is one span `name`."""
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kw):
                with self.span(name):
                    return fn(*args, **kw)
            return wrapper
        return deco

    def _finish_root(self, root: Span) -> None:
        with self._lock:
            self._traces.append(root)

    def attach_remote(self, span_dict: Optional[dict]) -> None:
        """Graft a serialized span subtree (from another process's tracer,
        carried over the RPC wire) under the current span, so a
        distributed search reads as ONE parent-child trace."""
        if not span_dict:
            return
        s = _current.get()
        if s is not None:
            with _attach_lock:
                s.remote_children = [*s.remote_children, span_dict]

    def wire_context(self) -> Optional[dict]:
        """Serializable trace context for cross-node propagation: the
        remote side stamps these onto its local root span so a grafted
        subtree stays attributable even when read from the remote node's
        own ring."""
        s = _current.get()
        if s is None:
            return None
        return {"trace_root_id": s.trace_id, "parent_span_id": s.span_id}

    def traces(self, limit: int = 20) -> List[dict]:
        with self._lock:
            items = list(self._traces)[-limit:]
        return [s.to_dict() for s in reversed(items)]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            # spans started so far, read off the id counter (ids are
            # issued lock-free on the span path): a read consumes one id,
            # so reads are counted and subtracted
            self._peeks += 1
            started = next(self._ids) - self._peeks
            return {"enabled": self.enabled, "spans": started,
                    "retained_traces": len(self._traces)}


# process-default tracer (one node per process, like the fielddata breaker)
TRACER = Tracer()
