"""Host span/interval algebra over positional postings.

Reference `index/query/Span*QueryBuilder.java` (Lucene SpanQuery family) and
`index/query/IntervalsSourceProvider.java` (Lucene intervals). The TPU split:
the HOT phrase path (match_phrase, simple span_near, intervals match) runs
the device pair-join in ops/positions.py; the full ALGEBRA — or/not/first/
containing/within/multi, interval all_of/any_of and filters — is evaluated
here on the host with vectorized numpy over the same positional postings,
producing a dense per-doc frequency vector the device program scores exactly
like a phrase (BM25 over sloppy frequency). Span queries are rare and
position-bound; their cost is the posting scan, which numpy does at memory
bandwidth — no per-doc iterator trees like the JVM.

A span set is (docs, starts, ends) arrays lex-sorted by (doc, start, end);
all combinators are O(n log n) sorts/searchsorteds.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from . import query_dsl as dsl

BIG = np.int64(1) << 32


class SpanSet(NamedTuple):
    docs: np.ndarray     # i64[n]
    starts: np.ndarray   # i64[n]
    ends: np.ndarray     # i64[n]  (exclusive)

    def key(self) -> np.ndarray:
        return self.docs * BIG + self.starts

    @staticmethod
    def empty() -> "SpanSet":
        z = np.empty(0, np.int64)
        return SpanSet(z, z.copy(), z.copy())


def _sorted(docs, starts, ends) -> SpanSet:
    order = np.lexsort((ends, starts, docs))
    return SpanSet(docs[order], starts[order], ends[order])


def _dedup(s: SpanSet) -> SpanSet:
    if len(s.docs) == 0:
        return s
    k = np.stack([s.docs, s.starts, s.ends])
    keep = np.ones(len(s.docs), bool)
    keep[1:] = np.any(k[:, 1:] != k[:, :-1], axis=0)
    return SpanSet(s.docs[keep], s.starts[keep], s.ends[keep])


def term_spans(seg, field: str, term: str) -> SpanSet:
    pb = seg.postings.get(field)
    if pb is None or pb.pos_starts is None:
        return SpanSet.empty()
    r = pb.row(term)
    if r < 0:
        return SpanSet.empty()
    a, b = pb.row_slice(r)
    counts = pb.pos_starts[a + 1: b + 1] - pb.pos_starts[a: b]
    docs = np.repeat(pb.doc_ids[a:b], counts).astype(np.int64)
    pos = pb.positions[pb.pos_starts[a]: pb.pos_starts[b]].astype(np.int64)
    return _sorted(docs, pos, pos + 1)


def rows_spans(seg, field: str, rows: np.ndarray) -> SpanSet:
    """Union of term spans for a set of vocab rows (span_multi expansions)."""
    pb = seg.postings.get(field)
    if pb is None or pb.pos_starts is None or len(rows) == 0:
        return SpanSet.empty()
    dparts, pparts = [], []
    for r in rows:
        a, b = pb.row_slice(int(r))
        counts = pb.pos_starts[a + 1: b + 1] - pb.pos_starts[a: b]
        dparts.append(np.repeat(pb.doc_ids[a:b], counts).astype(np.int64))
        pparts.append(pb.positions[pb.pos_starts[a]: pb.pos_starts[b]]
                      .astype(np.int64))
    docs = np.concatenate(dparts)
    pos = np.concatenate(pparts)
    return _sorted(docs, pos, pos + 1)


def or_spans(sets: List[SpanSet]) -> SpanSet:
    sets = [s for s in sets if len(s.docs)]
    if not sets:
        return SpanSet.empty()
    return _dedup(_sorted(np.concatenate([s.docs for s in sets]),
                          np.concatenate([s.starts for s in sets]),
                          np.concatenate([s.ends for s in sets])))


def _seg_suffix_min(values: np.ndarray, docs: np.ndarray) -> np.ndarray:
    """Per-doc suffix minimum: out[i] = min(values[i:j]) within doc i's run."""
    if not len(values):
        return values
    vmax = int(values.max())
    dmax = int(docs.max())
    rev_v = (vmax - values)[::-1]            # suffix-min -> prefix-max
    rev_g = (dmax - docs)[::-1]              # nondecreasing group ids
    out = _seg_cummax(rev_v, rev_g)
    return (vmax - out)[::-1]


def near_spans(sets: List[SpanSet], slop: int, in_order: bool) -> SpanSet:
    """Combine clause span sets like SpanNearQuery: one result span per
    first-clause anchor when every clause matches nearby; `slop` bounds the
    uncovered positions inside the combined span (gap count).

    Ordered: for each anchor, each next clause takes the valid span
    (start >= previous end, same doc) with the MINIMAL end — the
    interval-scheduling greedy, exact for ordered existence even with
    variable-width alternatives. Unordered: nearest span per clause around
    the anchor — exact when clauses don't compete for positions (the device
    phrase engine's documented relaxation)."""
    if not sets or any(len(s.docs) == 0 for s in sets):
        return SpanSet.empty()
    a = sets[0]
    docs, starts, ends = a.docs, a.starts, a.ends.copy()
    ok = np.ones(len(docs), bool)
    if in_order:
        width_used = ends - starts
        prev_end = ends.copy()
        for s in sets[1:]:
            key = s.key()
            smin_end = _seg_suffix_min(s.ends, s.docs)
            # second order (doc, end) -> recover the chosen span's start
            # (max start for that end = narrowest, still >= prev_end)
            o2 = np.lexsort((s.starts, s.ends, s.docs))
            key2 = s.docs[o2] * BIG + s.ends[o2]
            starts2 = s.starts[o2]
            idx = np.searchsorted(key, docs * BIG + prev_end, "left")
            safe = np.minimum(idx, len(key) - 1)
            found = (idx < len(key)) & (s.docs[safe] == docs)
            e_star = smin_end[safe]
            j2 = np.searchsorted(key2, docs * BIG + e_star, "right") - 1
            j2safe = np.maximum(j2, 0)
            s_star = starts2[j2safe]
            ok &= found
            prev_end = np.where(found, e_star, prev_end)
            width_used = width_used + np.where(found, e_star - s_star, 0)
        span_lo, span_hi = starts, prev_end
    else:
        span_lo = starts.copy()
        span_hi = ends.copy()
        width_used = ends - starts
        for s in sets[1:]:
            key = s.key()
            q = docs * BIG + starts
            idx = np.searchsorted(key, q, "left")
            ridx = np.minimum(idx, len(key) - 1)
            r_ok = (idx < len(key)) & (s.docs[ridx] == docs)
            lidx = np.maximum(idx - 1, 0)
            l_ok = (idx > 0) & (s.docs[lidx] == docs)
            rdist = np.where(r_ok, np.abs(s.starts[ridx] - starts), BIG)
            ldist = np.where(l_ok, np.abs(s.starts[lidx] - starts), BIG)
            pick = np.where(rdist <= ldist, ridx, lidx)
            found = r_ok | l_ok
            ok &= found
            span_lo = np.minimum(span_lo, np.where(found, s.starts[pick],
                                                   span_lo))
            span_hi = np.maximum(span_hi, np.where(found, s.ends[pick],
                                                   span_hi))
            width_used = width_used + np.where(
                found, s.ends[pick] - s.starts[pick], 0)
    gaps = (span_hi - span_lo) - width_used
    if slop >= 0:
        ok &= gaps <= slop
    keep = ok
    return _dedup(_sorted(docs[keep], span_lo[keep], span_hi[keep]))


_POS_RANGE = np.int64(1) << 22   # positions/ends < 2^22 (dl cap is 2^21)


def _seg_cummax(values: np.ndarray, docs: np.ndarray) -> np.ndarray:
    """Per-doc running maximum, vectorized: docs are nondecreasing, so
    cummax(v + doc*R) with R > value range restarts at each doc boundary
    (earlier docs' shifted values can never dominate)."""
    if not len(values):
        return values
    shifted = values + docs * _POS_RANGE
    return np.maximum.accumulate(shifted) - docs * _POS_RANGE


def not_spans(inc: SpanSet, exc: SpanSet, pre: int, post: int) -> SpanSet:
    """Include spans with no exclude span overlapping [start-pre, end+post)."""
    if len(inc.docs) == 0 or len(exc.docs) == 0:
        return inc
    # clamp windows to the position range so huge pre/post can't push the
    # packed (doc, pos) key into another doc's range
    pre = int(min(max(pre, 0), _POS_RANGE))
    post = int(min(max(post, 0), _POS_RANGE))
    key = exc.key()
    cmax_end = _seg_cummax(exc.ends, exc.docs)
    hi = np.searchsorted(key, inc.docs * BIG + (inc.ends + post), "left")
    has = hi > 0
    safe = np.maximum(hi - 1, 0)
    same_doc = exc.docs[safe] == inc.docs
    overlap = has & same_doc & (cmax_end[safe] > inc.starts - pre)
    keep = ~overlap
    return SpanSet(inc.docs[keep], inc.starts[keep], inc.ends[keep])


def first_spans(s: SpanSet, end: int) -> SpanSet:
    keep = s.ends <= end
    return SpanSet(s.docs[keep], s.starts[keep], s.ends[keep])


def containing_spans(big: SpanSet, little: SpanSet) -> SpanSet:
    """Big spans that fully contain at least one little span."""
    if len(big.docs) == 0 or len(little.docs) == 0:
        return SpanSet.empty()
    order = np.lexsort((little.starts, little.ends, little.docs))
    le_docs = little.docs[order]
    le_ends = little.ends[order]
    le_starts = little.starts[order]
    cmax_start = _seg_cummax(le_starts, le_docs)
    key = le_docs * BIG + le_ends
    hi = np.searchsorted(key, big.docs * BIG + big.ends, "right")
    has = hi > 0
    safe = np.maximum(hi - 1, 0)
    ok = has & (le_docs[safe] == big.docs) & (cmax_start[safe] >= big.starts)
    return SpanSet(big.docs[ok], big.starts[ok], big.ends[ok])


def within_spans(little: SpanSet, big: SpanSet) -> SpanSet:
    """Little spans fully contained in at least one big span."""
    if len(big.docs) == 0 or len(little.docs) == 0:
        return SpanSet.empty()
    cmax_end = _seg_cummax(big.ends, big.docs)
    key = big.key()
    hi = np.searchsorted(key, little.docs * BIG + little.starts, "right")
    has = hi > 0
    safe = np.maximum(hi - 1, 0)
    ok = has & (big.docs[safe] == little.docs) & \
        (cmax_end[safe] >= little.ends)
    return SpanSet(little.docs[ok], little.starts[ok], little.ends[ok])


def before_spans(s: SpanSet, f: SpanSet) -> SpanSet:
    """Spans that end at or before some filter span's start (intervals
    `before`)."""
    if len(s.docs) == 0 or len(f.docs) == 0:
        return SpanSet.empty()
    # per doc maximum filter start
    order = np.lexsort((f.starts, f.docs))
    fd = f.docs[order]
    fs = f.starts[order]
    cmax = _seg_cummax(fs, fd)
    key = fd * BIG + fs
    hi = np.searchsorted(key, s.docs * BIG + np.int64(BIG - 1), "left")
    has = hi > 0
    safe = np.maximum(hi - 1, 0)
    ok = has & (fd[safe] == s.docs) & (cmax[safe] >= s.ends)
    return SpanSet(s.docs[ok], s.starts[ok], s.ends[ok])


def after_spans(s: SpanSet, f: SpanSet) -> SpanSet:
    """Spans that start at or after some filter span's end."""
    if len(s.docs) == 0 or len(f.docs) == 0:
        return SpanSet.empty()
    order = np.lexsort((f.ends, f.docs))
    fd = f.docs[order]
    fe = f.ends[order]
    # per doc minimum filter end: reverse cummax trick via negation
    cmin = -_seg_cummax(-fe, fd)
    # index of FIRST entry for each doc: searchsorted on doc keys
    first_idx = np.searchsorted(fd, s.docs, "left")
    has = first_idx < len(fd)
    safe = np.minimum(first_idx, len(fd) - 1)
    ok = has & (fd[safe] == s.docs)
    # min end per doc = running min evaluated at the doc's LAST entry
    last_idx = np.searchsorted(fd, s.docs, "right") - 1
    lsafe = np.maximum(last_idx, 0)
    ok = ok & (cmin[lsafe] <= s.starts)
    return SpanSet(s.docs[ok], s.starts[ok], s.ends[ok])


def freq_vector(s: SpanSet, ndocs: int) -> np.ndarray:
    """Per-doc sloppy frequency Σ 1/(1 + width-1) over the final spans
    (Lucene SpanScorer's sloppyFreq accumulation)."""
    out = np.zeros(ndocs, np.float32)
    if len(s.docs):
        w = 1.0 / (1.0 + (s.ends - s.starts - 1).astype(np.float32))
        np.add.at(out, s.docs.astype(np.int64), w)
    return out


# ---------------------------------------------------------------------
# DSL tree evaluation
# ---------------------------------------------------------------------

class SpanEvalError(dsl.QueryParseError):
    pass


def eval_span_query(q, seg, ctx) -> Tuple[str, SpanSet, List[str]]:
    """-> (field, spans, terms involved) for a span query tree."""
    from . import plan as PL

    if isinstance(q, dsl.SpanTermQuery):
        term = PL.index_term(q.field, q.value, ctx)
        ft = ctx.mappings.resolve_field(q.field)
        field = ft.name if ft else q.field
        return field, term_spans(seg, field, term), [term]

    if isinstance(q, dsl.SpanNearQuery):
        parts = [eval_span_query(c, seg, ctx) for c in q.clauses]
        field = _one_field(parts, "span_near")
        spans = near_spans([p[1] for p in parts], q.slop, q.in_order)
        return field, spans, _terms(parts)

    if isinstance(q, dsl.SpanOrQuery):
        parts = [eval_span_query(c, seg, ctx) for c in q.clauses]
        field = _one_field(parts, "span_or")
        return field, or_spans([p[1] for p in parts]), _terms(parts)

    if isinstance(q, dsl.SpanNotQuery):
        fi, inc, ti = eval_span_query(q.include, seg, ctx)
        fe, exc, _te = eval_span_query(q.exclude, seg, ctx)
        if fi != fe:
            raise SpanEvalError("[span_not] clauses must share a field")
        return fi, not_spans(inc, exc, q.pre, q.post), ti

    if isinstance(q, dsl.SpanFirstQuery):
        f, s, t = eval_span_query(q.match, seg, ctx)
        return f, first_spans(s, q.end), t

    if isinstance(q, dsl.SpanContainingQuery):
        fb, big, tb = eval_span_query(q.big, seg, ctx)
        fl, little, _tl = eval_span_query(q.little, seg, ctx)
        if fb != fl:
            raise SpanEvalError("[span_containing] clauses must share a field")
        return fb, containing_spans(big, little), tb

    if isinstance(q, dsl.SpanWithinQuery):
        fb, big, _tb = eval_span_query(q.big, seg, ctx)
        fl, little, tl = eval_span_query(q.little, seg, ctx)
        if fb != fl:
            raise SpanEvalError("[span_within] clauses must share a field")
        return fl, within_spans(little, big), tl

    if isinstance(q, dsl.SpanMultiQuery):
        return _eval_span_multi(q, seg, ctx)

    if isinstance(q, dsl.FieldMaskingSpanQuery):
        # evaluate on the inner query's true field; report the masked field
        # so enclosing span_near accepts mixed-field clauses (reference
        # FieldMaskingSpanQuery)
        _f, s, t = eval_span_query(q.query, seg, ctx)
        ft = ctx.mappings.resolve_field(q.field)
        return (ft.name if ft else q.field), s, t

    raise SpanEvalError(
        f"[{type(q).__name__}] is not a span query")


def _eval_span_multi(q, seg, ctx):
    from . import plan as PL

    inner = q.match
    if isinstance(inner, dsl.PrefixQuery):
        field, expander = inner.field, PL.prefix_expander(
            inner.field, inner.value, False)
    elif isinstance(inner, dsl.WildcardQuery):
        field, expander = inner.field, PL.wildcard_expander(
            inner.field, inner.value, False)
    elif isinstance(inner, dsl.FuzzyQuery):
        field, expander = inner.field, PL.fuzzy_expander(
            inner.field, inner.value, inner.fuzziness, inner.prefix_length)
    elif isinstance(inner, dsl.RegexpQuery):
        field, expander = inner.field, PL.regexp_expander(
            inner.field, inner.value)
    else:
        raise SpanEvalError(
            "[span_multi] needs a prefix/wildcard/fuzzy/regexp query")
    ft = ctx.mappings.resolve_field(field)
    field = ft.name if ft else field
    rows = expander(seg)
    pb = seg.postings.get(field)
    terms = [pb.vocab[int(r)] for r in rows[:16]] if pb is not None else []
    return field, rows_spans(seg, field, rows), terms


def eval_interval_rule(rule: dsl.IntervalRule, field: str, seg, ctx
                       ) -> Tuple[SpanSet, List[str]]:
    from . import plan as PL

    if rule.kind == "match":
        terms = PL.analyze_query_text(field, rule.query, ctx, rule.analyzer)
        sets = [term_spans(seg, field, t) for t in terms]
        if len(sets) == 1:
            spans = sets[0]
        else:
            spans = near_spans(sets, rule.max_gaps, rule.ordered)
    elif rule.kind in ("prefix", "wildcard", "fuzzy"):
        if rule.kind == "prefix":
            expander = PL.prefix_expander(field, rule.query, False)
        elif rule.kind == "wildcard":
            expander = PL.wildcard_expander(field, rule.query, False)
        else:
            expander = PL.fuzzy_expander(field, rule.query, rule.fuzziness,
                                         rule.prefix_length)
        rows = expander(seg)
        pb = seg.postings.get(field)
        terms = [pb.vocab[int(r)] for r in rows[:16]] if pb is not None else []
        spans = rows_spans(seg, field, rows)
    elif rule.kind in ("all_of", "any_of"):
        parts = [eval_interval_rule(r, field, seg, ctx) for r in rule.rules]
        terms = [t for _s, ts in parts for t in ts]
        if rule.kind == "any_of":
            spans = or_spans([s for s, _t in parts])
        else:
            spans = near_spans([s for s, _t in parts], rule.max_gaps,
                               rule.ordered)
    else:
        raise SpanEvalError(f"unknown intervals rule [{rule.kind}]")

    if rule.filter_kind:
        fspans, _ft = eval_interval_rule(rule.filter_rule, field, seg, ctx)
        fk = rule.filter_kind
        if fk == "containing":
            spans = containing_spans(spans, fspans)
        elif fk == "contained_by":
            spans = within_spans(spans, fspans)
        elif fk == "not_containing":
            kept = containing_spans(spans, fspans)
            spans = _difference(spans, kept)
        elif fk == "not_contained_by":
            kept = within_spans(spans, fspans)
            spans = _difference(spans, kept)
        elif fk == "not_overlapping":
            spans = not_spans(spans, fspans, 0, 0)
        elif fk == "before":
            spans = before_spans(spans, fspans)
        elif fk == "after":
            spans = after_spans(spans, fspans)
    return spans, terms


def _difference(all_s: SpanSet, minus: SpanSet) -> SpanSet:
    """Set difference by tagged merge (exact for deduped span sets)."""
    if len(minus.docs) == 0 or len(all_s.docs) == 0:
        return all_s
    na = len(all_s.docs)
    docs = np.concatenate([all_s.docs, minus.docs])
    starts = np.concatenate([all_s.starts, minus.starts])
    ends = np.concatenate([all_s.ends, minus.ends])
    tag = np.concatenate([np.zeros(na, np.int8),
                          np.ones(len(minus.docs), np.int8)])
    src = np.concatenate([np.arange(na), np.full(len(minus.docs), -1)])
    order = np.lexsort((tag, ends, starts, docs))
    d, s, e, t, sr = (docs[order], starts[order], ends[order], tag[order],
                      src[order])
    dup_next = np.zeros(len(d), bool)
    dup_next[:-1] = ((d[:-1] == d[1:]) & (s[:-1] == s[1:])
                     & (e[:-1] == e[1:]) & (t[1:] == 1))
    removed_src = sr[(t == 0) & dup_next]
    keep = np.ones(na, bool)
    keep[removed_src] = False
    return SpanSet(all_s.docs[keep], all_s.starts[keep], all_s.ends[keep])


def collect_terms(query, ctx, cap: int = 16) -> List[str]:
    """Light term collection for the pseudo-term idf weight: no positional
    evaluation, only term-dict scans for expansions (cheap)."""
    from . import plan as PL

    out: List[str] = []

    def expand(field, make_expander):
        ft = ctx.mappings.resolve_field(field)
        f = ft.name if ft else field
        for seg in ctx.segments:
            pb = seg.postings.get(f)
            if pb is None:
                continue
            rows = make_expander(f)(seg)
            out.extend(pb.vocab[int(r)] for r in rows[:cap])

    def walk(q):
        if isinstance(q, dsl.SpanTermQuery):
            out.append(PL.index_term(q.field, q.value, ctx))
        elif isinstance(q, (dsl.SpanNearQuery, dsl.SpanOrQuery)):
            for c in q.clauses:
                walk(c)
        elif isinstance(q, dsl.SpanNotQuery):
            walk(q.include)
        elif isinstance(q, dsl.SpanFirstQuery):
            walk(q.match)
        elif isinstance(q, dsl.SpanContainingQuery):
            walk(q.big)
        elif isinstance(q, dsl.SpanWithinQuery):
            walk(q.little)
        elif isinstance(q, dsl.FieldMaskingSpanQuery):
            walk(q.query)
        elif isinstance(q, dsl.SpanMultiQuery):
            inner = q.match
            if isinstance(inner, dsl.PrefixQuery):
                expand(inner.field, lambda f: PL.prefix_expander(
                    f, inner.value, False))
            elif isinstance(inner, dsl.WildcardQuery):
                expand(inner.field, lambda f: PL.wildcard_expander(
                    f, inner.value, False))
            elif isinstance(inner, dsl.FuzzyQuery):
                expand(inner.field, lambda f: PL.fuzzy_expander(
                    f, inner.value, inner.fuzziness, inner.prefix_length))
            elif isinstance(inner, dsl.RegexpQuery):
                expand(inner.field, lambda f: PL.regexp_expander(
                    f, inner.value))

    def walk_rule(rule, field):
        if rule.kind == "match":
            out.extend(PL.analyze_query_text(field, rule.query, ctx,
                                             rule.analyzer))
        elif rule.kind == "prefix":
            expand(field, lambda f: PL.prefix_expander(f, rule.query, False))
        elif rule.kind == "wildcard":
            expand(field, lambda f: PL.wildcard_expander(f, rule.query, False))
        elif rule.kind == "fuzzy":
            expand(field, lambda f: PL.fuzzy_expander(
                f, rule.query, rule.fuzziness, rule.prefix_length))
        else:
            for r in rule.rules:
                walk_rule(r, field)

    if isinstance(query, tuple):
        walk_rule(query[2], query[1])
    else:
        walk(query)
    return out


def span_query_field(q, ctx) -> Optional[str]:
    """Structural validation without data: resolve the tree's single field
    (field-mismatch and shape errors surface on empty indices too)."""
    def resolve(f):
        ft = ctx.mappings.resolve_field(f)
        return ft.name if ft else f

    if isinstance(q, dsl.SpanTermQuery):
        return resolve(q.field)
    if isinstance(q, (dsl.SpanNearQuery, dsl.SpanOrQuery)):
        label = ("span_near" if isinstance(q, dsl.SpanNearQuery)
                 else "span_or")
        fields = {span_query_field(c, ctx) for c in q.clauses}
        fields.discard(None)
        if len(fields) > 1:
            raise SpanEvalError(f"[{label}] clauses must share a field")
        return next(iter(fields), None)
    if isinstance(q, dsl.SpanNotQuery):
        fi = span_query_field(q.include, ctx)
        fe = span_query_field(q.exclude, ctx)
        if fi is not None and fe is not None and fi != fe:
            raise SpanEvalError("[span_not] clauses must share a field")
        return fi
    if isinstance(q, dsl.SpanFirstQuery):
        return span_query_field(q.match, ctx)
    if isinstance(q, (dsl.SpanContainingQuery, dsl.SpanWithinQuery)):
        label = ("span_containing" if isinstance(q, dsl.SpanContainingQuery)
                 else "span_within")
        fb = span_query_field(q.big, ctx)
        fl = span_query_field(q.little, ctx)
        if fb is not None and fl is not None and fb != fl:
            raise SpanEvalError(f"[{label}] clauses must share a field")
        return fb or fl
    if isinstance(q, dsl.SpanMultiQuery):
        inner = q.match
        if not isinstance(inner, (dsl.PrefixQuery, dsl.WildcardQuery,
                                  dsl.FuzzyQuery, dsl.RegexpQuery)):
            raise SpanEvalError(
                "[span_multi] needs a prefix/wildcard/fuzzy/regexp query")
        return resolve(inner.field)
    if isinstance(q, dsl.FieldMaskingSpanQuery):
        span_query_field(q.query, ctx)   # validate inner shape
        return resolve(q.field)
    raise SpanEvalError(f"[{type(q).__name__}] is not a span query")


def _one_field(parts, label: str) -> str:
    fields = {p[0] for p in parts}
    if len(fields) != 1:
        raise SpanEvalError(f"[{label}] clauses must share a field")
    return next(iter(fields))


def _terms(parts) -> List[str]:
    return [t for p in parts for t in p[2]]
