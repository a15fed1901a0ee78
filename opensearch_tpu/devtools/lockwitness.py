"""Runtime lock-witness sanitizer (lockdep-style) — ISSUE 16 tentpole.

The static concurrency pass (devtools/oslint/concurrency) commits a
whole-program lock-order graph to `lock_order.json`; this module is the
execution half of the contract: an opt-in instrumentation layer that
wraps every lock the package creates, records the acquisition orders the
running process ACTUALLY exhibits, and flags an inversion — lock B
acquired while holding A after the opposite order was witnessed — the
moment it happens, naming both stacks, instead of waiting for the
one-in-a-million scheduling that turns the inversion into a deadlock.

Activation:
    OPENSEARCH_TPU_LOCKWITNESS=1         wrap + record (report only)
or programmatically `lockwitness.install(strict=...)` (tests; `strict`
also raises LockOrderInversion).

Mechanics: `install()` patches the `threading.Lock` / `threading.RLock`
factories. The replacement walks the creating stack frame (skipping
this module and threading.py — so a `threading.Condition()`'s inner
RLock attributes to the Condition call site) and wraps only locks
created inside the opensearch_tpu package (devtools excluded); the
witness key is the creation site `path:lineno`, which joins to the
static artifact's `declared` field so `verify_against()` can check the
observed order against the committed graph. Everything else gets a raw
lock — the witness never changes behavior outside the package.

Hot-path cost: per acquire, one thread-local list append plus one plain
dict membership probe per held lock (GIL-safe reads); the slow path
(first sighting of an edge — stack capture under an internal raw lock)
runs once per (held, acquired) pair per process. What that costs a
request on the chip is not measured.

Known modeling edges (shared with the static pass, see
docs/STATIC_ANALYSIS.md "Concurrency suite"): `Condition.wait()`
releases the underlying lock through the inner `_release_save` binding,
bypassing the witness — the waiting thread's held stack keeps the entry
until it wakes, which is sound (a blocked thread acquires nothing) but
means wait-reacquisition is not re-witnessed. Reentrant re-acquires of
an RLock are tracked for release pairing but never recorded as edges.
"""

from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import Dict, List, Optional, Tuple

# raw factories captured at import — the witness builds its own
# bookkeeping locks from these even while threading.* is patched
_RAW_LOCK = threading.Lock
_RAW_RLOCK = threading.RLock

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_DIR)
_DEVTOOLS_DIR = os.path.join(_PKG_DIR, "devtools")
_THREADING_FILE = os.path.abspath(threading.__file__)
_SELF_FILE = os.path.abspath(__file__)


class LockOrderInversion(RuntimeError):
    """Raised in strict mode when an acquisition order inversion is
    witnessed; carries the inversion record (both stacks)."""

    def __init__(self, record: dict) -> None:
        super().__init__(
            f"lock-order inversion: acquired {record['second']} while "
            f"holding {record['first']} after the opposite order was "
            f"witnessed at {record['prior_site']}")
        self.record = record


class _WitnessState:
    """All witness bookkeeping. One per install(); `armed` gates the
    hot path so uninstall() can disarm wrapped locks already in the
    wild without touching them."""

    def __init__(self, strict: bool) -> None:
        self.strict = strict
        self.armed = True
        self.tls = threading.local()
        # (first_key, second_key) -> first-sighting info (site + stack);
        # read lock-free on the hot path (GIL-atomic dict probe),
        # written only under `mu`
        self.edges: Dict[Tuple[str, str], dict] = {}
        self.inversions: List[dict] = []
        self._inverted_pairs: set = set()
        self.wrapped = 0
        self.mu = _RAW_LOCK()

    def held(self) -> List[str]:
        h = getattr(self.tls, "held", None)
        if h is None:
            h = self.tls.held = []
        return h


_STATE: Optional[_WitnessState] = None
_installed = False


def _stack(skip_self: bool = True) -> str:
    frames = traceback.extract_stack()
    if skip_self:
        frames = [f for f in frames
                  if os.path.abspath(f.filename) != _SELF_FILE]
    return "".join(traceback.format_list(frames[-12:]))


def _creation_site() -> Optional[str]:
    """Walk out of lockwitness/threading frames to the frame that
    called the lock factory; repo-relative `path:lineno`, or None when
    the creator is outside the package (or inside devtools)."""
    f = sys._getframe(1)
    while f is not None:
        fn = os.path.abspath(f.f_code.co_filename)
        if fn != _SELF_FILE and fn != _THREADING_FILE:
            break
        f = f.f_back
    if f is None:
        return None
    fn = os.path.abspath(f.f_code.co_filename)
    if not fn.startswith(_PKG_DIR + os.sep):
        return None
    if fn.startswith(_DEVTOOLS_DIR + os.sep):
        return None
    rel = os.path.relpath(fn, _REPO_ROOT).replace(os.sep, "/")
    return f"{rel}:{f.f_lineno}"


def _note_acquired(key: str) -> None:
    st = _STATE
    if st is None or not st.armed:
        return
    held = st.held()
    if key in held:
        held.append(key)       # reentrant: pair the release, no edge
        return
    for prev in held:
        if prev == key:
            continue
        edge = (prev, key)
        if edge not in st.edges:
            with st.mu:
                if edge not in st.edges:
                    st.edges[edge] = {
                        "site": _top_site(),
                        "stack": _stack(),
                        "thread": threading.current_thread().name,
                    }
        rev = st.edges.get((key, prev))
        if rev is not None:
            _note_inversion(st, prev, key, rev)
    held.append(key)


def _note_released(key: str) -> None:
    st = _STATE
    if st is None or not st.armed:
        return
    held = st.held()
    for i in range(len(held) - 1, -1, -1):
        if held[i] == key:
            del held[i]
            return


def _top_site() -> str:
    f = sys._getframe(1)
    while f is not None:
        fn = os.path.abspath(f.f_code.co_filename)
        if fn != _SELF_FILE and fn != _THREADING_FILE:
            return (os.path.relpath(fn, _REPO_ROOT).replace(os.sep, "/")
                    + f":{f.f_lineno}")
        f = f.f_back
    return "?"


def _note_inversion(st: _WitnessState, first: str, second: str,
                    rev_info: dict) -> None:
    pair = (min(first, second), max(first, second))
    record = {
        "first": first,             # held now
        "second": second,           # acquired now
        "site": _top_site(),
        "stack": _stack(),
        "thread": threading.current_thread().name,
        "prior_site": rev_info.get("site", "?"),
        "prior_stack": rev_info.get("stack", ""),
        "prior_thread": rev_info.get("thread", "?"),
    }
    fresh = False
    with st.mu:
        if pair not in st._inverted_pairs:
            st._inverted_pairs.add(pair)
            fresh = True
        st.inversions.append(record)
    if fresh:
        # freeze the flight recorder: a witnessed inversion is exactly
        # the kind of once-in-a-blue-moon evidence the black box exists
        # for. Lazy import + best-effort: the witness must never take
        # the process down on a recorder problem (unless strict).
        try:
            from ..obs.flight_recorder import RECORDER
            RECORDER.note_lock_inversion(
                first, second, record["stack"], record["prior_stack"])
        except Exception:
            pass
    if st.strict:
        raise LockOrderInversion(record)


class WitnessLock:
    """Transparent proxy: forwards to the wrapped lock, reporting
    successful acquire/release transitions to the witness."""

    __slots__ = ("_inner", "_key")

    def __init__(self, inner, key: str) -> None:
        self._inner = inner
        self._key = key

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            try:
                _note_acquired(self._key)
            except LockOrderInversion:
                # strict mode raises out of the bookkeeping AFTER the
                # inner lock was taken; propagating without releasing
                # would leave it held forever — turning the report into
                # the very deadlock it exists to prevent. The key was
                # never pushed onto the thread's held stack (the raise
                # happens before the append), so no _note_released here.
                self._inner.release()
                raise
        return ok

    def release(self) -> None:
        self._inner.release()
        _note_released(self._key)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False

    def locked(self) -> bool:
        return self._inner.locked()

    def __getattr__(self, name: str):
        # Condition() binds _release_save/_acquire_restore/_is_owned
        # straight off the inner lock — wait() bypasses the witness by
        # design (see module docstring)
        return getattr(self._inner, name)

    def __repr__(self) -> str:
        return f"<WitnessLock {self._key} {self._inner!r}>"


def wrap(lock, key: str):
    """Explicitly wrap an existing lock under `key` (tests, fixtures)."""
    st = _STATE
    if st is not None:
        with st.mu:
            st.wrapped += 1
    return WitnessLock(lock, key)


def _factory(raw):
    def make(*args, **kwargs):
        inner = raw(*args, **kwargs)
        st = _STATE
        if st is None or not st.armed:
            return inner
        site = _creation_site()
        if site is None:
            return inner
        with st.mu:
            st.wrapped += 1
        return WitnessLock(inner, site)
    make._lockwitness = True  # type: ignore[attr-defined]
    return make


def install(strict: bool = False) -> _WitnessState:
    """Arm the witness and patch the threading lock factories.
    Idempotent; returns the active state (for tests)."""
    global _STATE, _installed
    if _STATE is not None and _STATE.armed:
        _STATE.strict = bool(strict)
        return _STATE
    _STATE = _WitnessState(bool(strict))
    if not _installed:
        threading.Lock = _factory(_RAW_LOCK)        # type: ignore
        threading.RLock = _factory(_RAW_RLOCK)      # type: ignore
        _installed = True
    return _STATE


def uninstall() -> None:
    """Restore the raw factories and disarm. Locks already wrapped stay
    functional (the proxy forwards); they just stop reporting."""
    global _STATE, _installed
    if _installed:
        threading.Lock = _RAW_LOCK                  # type: ignore
        threading.RLock = _RAW_RLOCK                # type: ignore
        _installed = False
    if _STATE is not None:
        _STATE.armed = False
    _STATE = None


def reset() -> None:
    """Drop recorded edges/inversions, keep the witness armed."""
    st = _STATE
    if st is None:
        return
    with st.mu:
        st.edges.clear()
        st.inversions.clear()
        st._inverted_pairs.clear()


def active() -> bool:
    return _STATE is not None and _STATE.armed


def edges() -> Dict[Tuple[str, str], dict]:
    st = _STATE
    if st is None:
        return {}
    with st.mu:
        return dict(st.edges)


def inversions() -> List[dict]:
    st = _STATE
    if st is None:
        return []
    with st.mu:
        return list(st.inversions)


def verify_against(graph_path: str) -> dict:
    """Check the witnessed acquisition orders against the committed
    static lock-order graph (`lock_order.json`).

    Runtime keys are creation sites (`path:lineno`); the static
    artifact's `declared` field carries the same site for every lock the
    inventory resolved, so the join is exact where the model is. Returns:

      order_conflicts  runtime edge (a, b) whose REVERSE (b, a) is in
                       the committed graph while (a, b) is not — the
                       witnessed order contradicts the model
      unmodeled_edges  runtime edge between two modeled locks that the
                       graph has in neither direction — the model is
                       missing an interleaving (file an issue or
                       regenerate the artifact)
      unmapped         runtime keys with no static declaration (locks
                       the inventory collapsed into attr:: nodes, or
                       fixture/wrap() keys)
    """
    import json
    with open(graph_path, "r", encoding="utf-8") as fh:
        graph = json.load(fh)
    decl_to_id = {l["declared"]: l["id"] for l in graph.get("locks", [])
                  if l.get("declared")}
    static_edges = {(e["from"], e["to"]) for e in graph.get("edges", [])}
    conflicts, unmodeled, unmapped = [], [], set()
    for (a, b), info in sorted(edges().items()):
        ia, ib = decl_to_id.get(a), decl_to_id.get(b)
        if ia is None:
            unmapped.add(a)
        if ib is None:
            unmapped.add(b)
        if ia is None or ib is None or ia == ib:
            continue
        if (ia, ib) in static_edges:
            continue
        entry = {"from": a, "to": b, "from_id": ia, "to_id": ib,
                 "site": info.get("site", "?")}
        if (ib, ia) in static_edges:
            conflicts.append(entry)
        else:
            unmodeled.append(entry)
    return {"order_conflicts": conflicts, "unmodeled_edges": unmodeled,
            "unmapped": sorted(unmapped)}
