"""Derived (runtime) fields: query-time fields computed by painless-lite
scripts over `_source` and doc values.

Reference analog: `index/mapper/DerivedFieldMapper.java` + the `derived`
mapping/search-body sections. The reference evaluates the script per doc
inside each query's iterator; the TPU design instead MATERIALIZES the
derived field once per (segment, script) into ordinary columns (+ a
postings block for keyword types), then lets every query, sort, agg, and
fetch run the normal device path at full speed — per-segment scripts are
host work, query execution stays vectorized. Materializations are cached
on the immutable segment and never persisted (flush skips derived names;
a changed script definition rebuilds).

Script convention: `emit(value)` (single emit) or a plain `return`; doc
values are reachable as `doc['field'].value` and the raw document as
`params._source` / `_source` (reference derived-field script contexts).
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, Dict, List, Optional

import numpy as np

from ..index.mappings import _parse_date
from ..script import painless_lite as pl

_EMIT_RE = re.compile(r"\bemit\s*\(")

DERIVED_TYPES = {"keyword", "long", "date", "double", "boolean"}


class DerivedField:
    __slots__ = ("name", "type", "source", "fmt")

    def __init__(self, name: str, type_: str, source: str,
                 fmt: Optional[str] = None):
        if type_ not in DERIVED_TYPES:
            raise ValueError(
                f"unsupported derived field type [{type_}] for [{name}] "
                f"(supported: {sorted(DERIVED_TYPES)})")
        self.name = name
        self.type = type_
        self.source = source
        self.fmt = fmt

    @property
    def digest(self) -> str:
        return hashlib.blake2b(
            f"{self.type}\x00{self.source}\x00{self.fmt}".encode(),
            digest_size=12).hexdigest()


class MappingsOverlay:
    """Per-request view of an index's Mappings with extra (search-body)
    derived definitions — shared Mappings are never mutated."""

    def __init__(self, base, extra_defs: Dict[str, "DerivedField"]):
        self._base = base
        self.derived = {**base.derived, **extra_defs}

    def resolve_field(self, name: str):
        from ..index.mappings import Mappings
        return Mappings.resolve_field(self, name)

    def __getattr__(self, k):
        return getattr(self._base, k)


def parse_defs(section: Optional[dict]) -> Dict[str, DerivedField]:
    """A mapping/search-body `derived` section -> DerivedField defs."""
    out: Dict[str, DerivedField] = {}
    for name, cfg in (section or {}).items():
        script = cfg.get("script", {})
        src = script.get("source", script) if isinstance(script, dict) \
            else script
        if not isinstance(src, str) or not src:
            raise ValueError(f"derived field [{name}] needs a script source")
        out[name] = DerivedField(name, cfg.get("type", "keyword"), src,
                                 cfg.get("format"))
    return out


def _emit_to_return(src: str) -> str:
    """Single-`emit(v)` scripts become return-style for the host
    interpreter (multi-emit arrays are not supported — documented)."""
    return _EMIT_RE.sub("return (", src)


def check_conflicts(mappings, defs: Dict[str, DerivedField]) -> None:
    """A derived field must not shadow a mapped field — materialization
    would clobber the real column on the shared segment (and flush would
    then skip persisting it)."""
    from ..index.mappings import Mappings
    for name in defs:
        base = mappings._base if isinstance(mappings, MappingsOverlay) \
            else mappings
        if name in base.fields:
            raise ValueError(
                f"derived field [{name}] conflicts with a mapped field")
        if "." in name:
            parent, sub = name.rsplit(".", 1)
            pft = base.fields.get(base.aliases.get(parent, parent))
            if pft is not None and sub in pft.subfields:
                raise ValueError(
                    f"derived field [{name}] conflicts with a mapped field")


def referenced(defs: Dict[str, DerivedField], body: dict) -> List[str]:
    """Derived names that appear anywhere in the request body — a cheap
    over-approximation; materializing an unreferenced field is only wasted
    host work, never a correctness issue."""
    import json
    blob = json.dumps(body, default=str)
    return [n for n in defs if n in blob]


# msearch's per-body fallback runs searches on a thread pool; materialization
# mutates segment postings/column dicts, so two bodies referencing the same
# derived field must not interleave (coarse lock: it's a once-per-(segment,
# digest) cost)
_ENSURE_LOCK = __import__("threading").RLock()


def ensure(seg, mappings, defs: Dict[str, DerivedField],
           names: List[str]) -> None:
    """Materialize the named derived fields on one segment (idempotent per
    script digest)."""
    with _ENSURE_LOCK:
        built: Dict[str, str] = seg.__dict__.setdefault("_derived_built", {})
        derived_names: set = seg.__dict__.setdefault("_derived_names", set())
        changed = False
        for name in names:
            df = defs[name]
            if built.get(name) == df.digest:
                continue
            _materialize(seg, mappings, df)
            built[name] = df.digest
            derived_names.add(name)
            changed = True
        if changed:
            _purge_query_caches(seg, names)


def _purge_query_caches(seg, names: List[str]) -> None:
    """A rematerialized derived field invalidates every cache derived from
    its old column: the device pytree, per-field device arrays, cached
    filter masks and fastpath filter lists/aligned layouts, sort ordinals,
    and date buckets."""
    from . import compiler as C, planes as PN
    from . import fastpath as FP

    # SWAP, don't clear in place: Segment.device_arrays readers hold a
    # snapshot reference to the dict and rely on its entries staying put
    # (same contract as drop_device / pressure eviction). Release the
    # dropped caches' ledger charges NOW, like drop_device does — the
    # rebuild registers a fresh set, and stale live charges would read
    # as ~2x the segment's footprint to the breaker, driving premature
    # pressure eviction (or trips) of other tenants
    from ..obs.hbm_ledger import LEDGER
    seg._device_cache = {}
    seg._device_positions = {}
    seg._device_live_dirty = {}
    seg.__dict__.pop("_field_device_cache", None)
    for allocs in seg.__dict__.pop("_hbm_allocs", {}).values():
        for alloc in allocs:
            LEDGER.release(alloc)
    for alloc in seg.__dict__.pop("_field_device_allocs", {}).values():
        LEDGER.release(alloc)
    C.purge_masks_for_uid(seg.uid)
    FP._purge_filtered_for_uid(seg.uid)
    seg.__dict__.get("_fastpath_filters", {}).clear()
    for name in names:
        seg.__dict__.get("_fastpath_aligned", {}).pop(name, None)
        # sort ranks (a nested sort's key among them), date buckets
        PN.drop_segment_planes(seg, name)


class _LazyDocCols(dict):
    """doc['field'] view materialized on access — scripts usually read one
    or two fields, so per-doc eager extraction of every column would
    dominate materialization time."""

    def __init__(self, seg, doc: int):
        super().__init__()
        self._seg = seg
        self._doc = doc

    def get(self, f, default=None):
        # the host interpreter reads dicts via .get(), which skips
        # __missing__ — route it through item access
        try:
            return self[f]
        except KeyError:
            return default

    def __missing__(self, f):
        seg, d = self._seg, self._doc
        col = seg.numeric_cols.get(f)
        if col is not None:
            vals = ([] if not col.present[d] else
                    [float(col.values[d]) if col.kind == "float"
                     else int(col.values[d])])
            v = self[f] = pl.HostDocValue(vals)
            return v
        kcol = seg.keyword_cols.get(f)
        if kcol is not None:
            a, b = int(kcol.starts[d]), int(kcol.starts[d + 1])
            v = self[f] = pl.HostDocValue(
                [kcol.vocab[o] for o in kcol.ords[a:b]])
            return v
        raise KeyError(f)


def _doc_env(seg, doc: int, src: dict) -> Dict[str, Any]:
    return {"doc": _LazyDocCols(seg, doc), "params": {"_source": src},
            "_source": src}


def _materialize(seg, mappings, df: DerivedField) -> None:
    ast = pl.parse(_emit_to_return(df.source))
    n = seg.ndocs
    raw: List[Any] = [None] * n
    for d in range(n):
        if not seg.live[d]:
            continue
        try:
            raw[d] = pl.execute(ast, _doc_env(seg, d, seg.sources[d]))
        except pl.ScriptError as e:
            raise pl.ScriptError(
                f"[{df.name}] failed on doc {d}: {e}") from e
    if df.type == "keyword":
        _install_keyword(seg, df.name, raw)
    else:
        _install_numeric(seg, df, raw)


def _coerce(df: DerivedField, v: Any):
    if v is None:
        return None
    if df.type == "long":
        return int(v)
    if df.type == "double":
        return float(v)
    if df.type == "boolean":
        return 1 if bool(v) else 0
    if df.type == "date":
        return _parse_date(v, df.fmt)
    return v


def _install_numeric(seg, df: DerivedField, raw: List[Any]) -> None:
    from ..index.segment import NumericColumn

    kind = "float" if df.type == "double" else "int"
    values = np.zeros(seg.ndocs,
                      np.float64 if kind == "float" else np.int64)
    present = np.zeros(seg.ndocs, bool)
    for d, v in enumerate(raw):
        cv = _coerce(df, v)
        if cv is None:
            continue
        values[d] = cv
        present[d] = True
    seg.numeric_cols[df.name] = NumericColumn(df.name, kind, values, present)


def _install_keyword(seg, name: str, raw: List[Any]) -> None:
    from ..index.segment import KeywordColumn, PostingsBlock

    svals = [None if v is None else str(v) for v in raw]
    vocab = sorted({v for v in svals if v is not None})
    ord_of = {v: i for i, v in enumerate(vocab)}
    n = seg.ndocs
    starts = np.zeros(n + 1, np.int64)
    flat_ords: List[int] = []
    flat_docs: List[int] = []
    min_ord = np.full(n, -1, np.int32)
    for d, v in enumerate(svals):
        starts[d + 1] = starts[d] + (0 if v is None else 1)
        if v is not None:
            o = ord_of[v]
            flat_ords.append(o)
            flat_docs.append(d)
            min_ord[d] = o
    seg.keyword_cols[name] = KeywordColumn(
        field=name, vocab=vocab, starts=starts,
        ords=np.asarray(flat_ords, np.int32),
        doc_of_value=np.asarray(flat_docs, np.int32), min_ord=min_ord)
    # postings so term/terms/match/exists queries ride the normal path:
    # one row per vocab value, doc-sorted (values appended doc-ascending)
    by_term: Dict[int, List[int]] = {}
    for o, d in zip(flat_ords, flat_docs):
        by_term.setdefault(o, []).append(d)
    pstarts = np.zeros(len(vocab) + 1, np.int64)
    docs_parts: List[int] = []
    for o in range(len(vocab)):
        row = by_term.get(o, [])
        pstarts[o + 1] = pstarts[o] + len(row)
        docs_parts.extend(row)
    seg.postings[name] = PostingsBlock(
        field=name, vocab=list(vocab), terms=dict(ord_of),
        starts=pstarts, doc_ids=np.asarray(docs_parts, np.int32),
        tfs=np.ones(len(docs_parts), np.float32))
