"""Plain highlighter. Analog of reference
`search/fetch/subphase/highlight/PlainHighlighter.java`: re-analyzes the
stored field text, marks query-term occurrences, and emits the best
fragments."""

from __future__ import annotations

from typing import Dict, List, Set

from ..analysis import Analyzer


def highlight_field(text: str, terms: Set[str], analyzer: Analyzer,
                    pre_tag: str = "<em>", post_tag: str = "</em>",
                    fragment_size: int = 100, number_of_fragments: int = 5) -> List[str]:
    # terms ending in "*" are prefixes (match_phrase_prefix's last position)
    exact = {t for t in terms if not t.endswith("*")}
    prefixes = tuple(t[:-1] for t in terms if t.endswith("*") and len(t) > 1)
    tokens = analyzer.analyze(text)
    hits = [(t.start_offset, t.end_offset) for t in tokens
            if t.text in exact or (prefixes and t.text.startswith(prefixes))]
    if not hits:
        return []
    if number_of_fragments == 0:
        # highlight whole field
        return [_mark(text, hits, pre_tag, post_tag)]
    # greedy fragmenting: grow a window around consecutive hits
    fragments: List[tuple] = []
    cur: List[tuple] = []
    for h in hits:
        if cur and h[1] - cur[0][0] > fragment_size:
            fragments.append(tuple(cur))
            cur = []
        cur.append(h)
    if cur:
        fragments.append(tuple(cur))
    out = []
    for frag_hits in fragments[:number_of_fragments]:
        s = max(0, frag_hits[0][0] - (fragment_size - (frag_hits[-1][1] - frag_hits[0][0])) // 2)
        e = min(len(text), s + max(fragment_size, frag_hits[-1][1] - frag_hits[0][0]))
        rel = [(a - s, b - s) for a, b in frag_hits if a >= s and b <= e]
        out.append(_mark(text[s:e], rel, pre_tag, post_tag))
    return out


def highlight_fvh(text: str, terms: Set[str],
                  tv_entries: List[tuple],
                  pre_tag: str = "<em>", post_tag: str = "</em>",
                  fragment_size: int = 100,
                  number_of_fragments: int = 5) -> List[str]:
    """Real FastVectorHighlighter path (reference
    `search/fetch/subphase/highlight/FastVectorHighlighter`): hit offsets
    come from the PERSISTED term vectors (term_vector=with_positions_offsets
    at index time), no re-analysis, and fragments rank by match count
    (score-ordered like the reference's ScoreOrderFragmentsBuilder)."""
    exact = {t for t in terms if not t.endswith("*")}
    prefixes = tuple(t[:-1] for t in terms if t.endswith("*") and len(t) > 1)
    hits = sorted(
        (s, e) for term, _pos, s, e in tv_entries
        if (term in exact or (prefixes and term.startswith(prefixes)))
        and 0 <= s and e <= len(text))
    if not hits:
        return []
    if number_of_fragments == 0:
        return [_mark(text, hits, pre_tag, post_tag)]
    fragments: List[tuple] = []
    cur: List[tuple] = []
    for h in hits:
        if cur and h[1] - cur[0][0] > fragment_size:
            fragments.append(tuple(cur))
            cur = []
        cur.append(h)
    if cur:
        fragments.append(tuple(cur))
    # FVH scores fragments: most matches first (stable on position)
    fragments.sort(key=lambda fr: -len(fr))
    out = []
    for frag_hits in fragments[:number_of_fragments]:
        s = max(0, frag_hits[0][0]
                - (fragment_size - (frag_hits[-1][1] - frag_hits[0][0])) // 2)
        e = min(len(text), s + max(fragment_size,
                                   frag_hits[-1][1] - frag_hits[0][0]))
        rel = [(a - s, b - s) for a, b in frag_hits if a >= s and b <= e]
        out.append(_mark(text[s:e], rel, pre_tag, post_tag))
    return out


def highlight_unified(text: str, terms: Set[str], analyzer: Analyzer,
                      pre_tag: str = "<em>", post_tag: str = "</em>",
                      fragment_size: int = 100,
                      number_of_fragments: int = 5) -> List[str]:
    """Unified-highlighter analog (reference
    `subphase/highlight/UnifiedHighlighter.java` over Lucene's passage
    formatter): sentence-bounded passages scored by distinct matched terms
    (unique-term coverage first, then hit count), best passages returned in
    score order."""
    exact = {t for t in terms if not t.endswith("*")}
    prefixes = tuple(t[:-1] for t in terms if t.endswith("*") and len(t) > 1)
    tokens = analyzer.analyze(text)
    hits = [(t.start_offset, t.end_offset, t.text) for t in tokens
            if t.text in exact or (prefixes and t.text.startswith(prefixes))]
    if not hits:
        return []
    if number_of_fragments == 0:
        return [_mark(text, [(a, b) for a, b, _ in hits], pre_tag, post_tag)]
    # sentence-ish passage boundaries, merged up to ~fragment_size
    bounds = [0]
    for i, ch in enumerate(text):
        if ch in ".!?\n":
            bounds.append(i + 1)
    if bounds[-1] != len(text):
        bounds.append(len(text))
    passages: List[tuple] = []
    s = bounds[0]
    for e in bounds[1:]:
        if e - s >= fragment_size and s != e:
            passages.append((s, e))
            s = e
    if s < len(text):
        passages.append((s, len(text)))
    scored = []
    for (a, b) in passages:
        ph = [(ha, hb, tt) for ha, hb, tt in hits if ha >= a and hb <= b]
        if not ph:
            continue
        uniq = len({tt for _, _, tt in ph})
        scored.append((uniq, len(ph), a, b, ph))
    scored.sort(key=lambda x: (-x[0], -x[1], x[2]))
    out = []
    for _u, _n, a, b, ph in scored[:number_of_fragments]:
        rel = [(ha - a, hb - a) for ha, hb, _ in ph]
        out.append(_mark(text[a:b], rel, pre_tag, post_tag))
    return out


def _mark(text: str, spans: List[tuple], pre: str, post: str) -> str:
    out = []
    prev = 0
    for a, b in spans:
        out.append(text[prev:a])
        out.append(pre)
        out.append(text[a:b])
        out.append(post)
        prev = b
    out.append(text[prev:])
    return "".join(out)


def collect_query_terms(lnode) -> Dict[str, Set[str]]:
    """field -> query terms, walked from the logical plan (for highlighting)."""
    from .plan import (LBool, LBoosting, LConstScore, LDisMax, LFuncScore,
                       LPhrase, LTerms)

    out: Dict[str, Set[str]] = {}

    def walk(n):
        if n is None:
            return
        if isinstance(n, LPhrase):
            s = out.setdefault(n.field, set())
            s.update(n.terms[:-1] if n.prefix_last else n.terms)
            if n.prefix_last:
                s.add(n.terms[-1] + "*")  # "*" suffix marks a prefix match
        elif isinstance(n, LTerms):
            out.setdefault(n.field, set()).update(n.terms)
        elif isinstance(n, LBool):
            for c in n.musts + n.shoulds + n.filters:
                walk(c)
        elif isinstance(n, LConstScore):
            walk(n.child)
        elif isinstance(n, LDisMax):
            for c in n.children:
                walk(c)
        elif isinstance(n, LBoosting):
            walk(n.positive)
        elif isinstance(n, LFuncScore):
            walk(n.child)

    walk(lnode)
    return out
