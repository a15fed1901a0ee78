"""Device-side positional joins: phrase / span-near matching on the TPU.

Replaces Lucene's ExactPhraseMatcher / SloppyPhraseMatcher doc-at-a-time
position merging (reference: `search/` via Lucene PhraseQuery,
SpanNearQuery) with a fully vectorized formulation:

- A text field's positions live on the device as two planes of the segment
  (`Segment.device_positions`: `doc` and `pos`, one slot a position, in
  postings order). A term's positions are contiguous there and
  sorted by (doc, position), so a query term is a WINDOW of the planes: an
  offset and a length, two scalars a request. (A `match_phrase_prefix`
  whose last term expands to several rows is the one term that is no
  window: its union is merged on the host and handed over as an array of
  its own, which the same code reads as a window of itself.)
- One term's window holds the *candidate anchors*: a sloppy or span query's
  first term, an exact phrase's term of fewest positions (every exact
  occurrence has one position of every slot, so the count is the same
  whichever slot anchors, and the shifts follow). For every anchor (d,
  base) we search each other term's window for the nearest adjusted
  position in the same doc; the per-term displacement |p_adj - base| is
  that term's move cost. A phrase occurrence exists when every term occurs
  in the doc and the total move cost <= slop (at slop 0: every term
  stands at its own place, whichever slot anchors).
- The per-anchor weight 1/(1+cost) is Lucene's sloppyFreq; scatter-adding it
  per doc yields the phrase frequency that feeds the normal BM25 tf curve.
- The search is `ROW`-ary and every probe of it reads a whole row. Beside
  each plane the segment holds its FENCE levels (`fences`): level k is every
  `ROW**k`-th slot of the plane. A term's window is sorted and a fence entry
  is a pair of the plane, so the entries whose slot lies inside the window
  are sorted too; the others are other terms' and are masked by slot index,
  never by value. The descent starts at the first level whose entries inside
  the window number `ROW` or fewer (one slice a request, compared against
  every anchor densely) and reads, a level below, the one aligned row of
  `ROW` entries under the child it chose: a row gather a plane, where a
  binary search read one element a round. On this chip a gather costs by
  the index, not by the byte (PERF.md, PR 48).

Everything is static-shaped: the anchor window is a slice of a power-of-four
bucket of slots and the search a descent of `search_levels` levels (compare
on (doc, pos) i32 pairs — no 64-bit keys needed), so one XLA program serves
all phrase queries of one `phrase_shape`: at most `len(ANCHOR_BUCKETS) *
len(SEARCH_LEVELS)` programs a term count and segment shape. The stages name
themselves in the device trace (`jax.named_scope`,
under the executor's prefix, inside its `executor.match`):
`executor.phrase_join` (the searches and the cost),
`executor.phrase_accumulate` (the scatter-add into the document plane),
`executor.phrase_score` (tf curve, mask).

Semantics note (documented deviation): Lucene's SloppyPhraseMatcher computes
the minimal *total* movement over a simultaneous alignment, with repeats
handled via restarts. The per-term nearest-position relaxation here equals it
whenever terms don't compete for the same position (the overwhelmingly common
case) and is otherwise a superset that still respects the total-slop bound
per anchor.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INT32_SENTINEL = np.int32(2**31 - 1)
# plain numpy scalar, NOT jnp: a module-level jax.Array would be captured as
# a device-resident trace constant, which the jit fast path can hoist into an
# extra executable parameter and then under-supply buffers on cache hits
BIG_COST = np.float32(1e9)


# what a probe of the search reads: one row of the planes' [slots / ROW, ROW]
# view (the 1-D tile itself). Wider rows were timed on the chip and cost more
# end to end (tests_tpu/test_phrase_tpu.py; PERF.md, PR 48)
ROW_BITS = 7
ROW = 1 << ROW_BITS
# the anchors a pass of the search walks: a gathered [PASS, ROW] int32 block
# is 32 MB, whatever the anchor's bucket (2^18 and 2^20 a pass were timed on
# the chip and cost a tenth more a search: PERF.md, PR 48)
PASS = 1 << 16
# the shapes a phrase program is compiled for: the anchor's window is padded
# to a power of four of slots, the searches descend as many levels as the
# largest other window takes (the planes' slots are counted in int32, so no
# window passes 2^31: ROW**5 is beyond it)
ANCHOR_BUCKETS = tuple(1 << e for e in range(6, 32, 2))
SEARCH_LEVELS = (1, 2, 3, 4, 5)
JOIN_SCOPE = "executor.phrase_join"
ACCUMULATE_SCOPE = "executor.phrase_accumulate"
SCORE_SCOPE = "executor.phrase_score"


def anchor_bucket(n: int) -> int:
    """Slots of the anchor window of `n` positions: the next power of four,
    64 at least."""
    return next((b for b in ANCHOR_BUCKETS if b >= n), 1 << 31)


def search_levels(n: int) -> int:
    """Levels of the `ROW`-ary search over a window of `n` positions: the
    least k with ROW**k >= n, 1 at least."""
    return next(k for k in SEARCH_LEVELS if n <= ROW ** k)


def phrase_shape(lens) -> Tuple[int, int]:
    """(anchor bucket, search levels) of a phrase whose terms hold `lens`
    positions, the anchor's first: the part of a phrase program's key that
    follows the terms (with the term count and the planes' own shape)."""
    return (anchor_bucket(int(lens[0])),
            search_levels(max([int(n) for n in lens[1:]] or [1])))


def probe_rows(bucket: int, nothers: int, levels: int) -> int:
    """Rows the join gathers for one phrase: below its top level (a slice)
    a search reads one row a plane, level and anchor slot."""
    return bucket * nothers * 2 * (levels - 1)


def probe_elems(bucket: int, nothers: int, levels: int) -> int:
    """Indices the join gathers one at a time for one phrase: a row of the
    search is one index (`probe_rows`), and the (doc, position) of the slot
    a search lands on and of its left neighbour are read an element each
    (the nearest of the two)."""
    return probe_rows(bucket, nothers, levels) + bucket * nothers * 4


def _whole_rows(level: jnp.ndarray) -> jnp.ndarray:
    """`level`, sentinel-padded to a whole number of rows where it is none
    (a resident plane is one: `index.segment.position_slots`)."""
    pad = -level.shape[0] % ROW
    return jnp.pad(level, (0, pad), constant_values=INT32_SENTINEL) \
        if pad else level


def fences(plane: jnp.ndarray, levels: Optional[int] = None) -> tuple:
    """The fence levels of a plane of (doc or position) slots: level k is
    every ROW**k-th slot of it, in whole rows. As many as a search over
    the whole plane descends below its top (`search_levels` of its length,
    less one), or `levels - 1`. A strided slice: on the device where the
    plane is there (`Segment.device_positions` keeps a field's beside its
    planes), in the program where the plane is the program's own
    (`whole`)."""
    if levels is None:
        levels = search_levels(plane.shape[0])
    out, level = [], plane
    for _ in range(1, levels):      # level k from level k - 1: one pass
        level = level[::ROW]        # over the plane, not one a level
        out.append(_whole_rows(level))
    return tuple(out)


def plane_key(plane: str, k: int) -> str:
    """The key of level k of `plane` ("doc" or "pos") in the flat dict
    `Segment.device_positions` returns: the plane itself at 0."""
    return plane if k == 0 else f"{plane}_f{k}"


def plane_keys(levels: int):
    """The keys of what a search of `levels` levels reads: the planes, and
    their fence levels below `levels`."""
    return tuple(plane_key(plane, k)
                 for plane in ("doc", "pos") for k in range(levels))


class Window(NamedTuple):
    """A term's positions: slots [lo, lo + n) of the planes (d, p), which
    are sorted by (doc, position) there. `fd` / `fp` are the planes' fence
    levels the search descends through (`fences`; fd[k - 1] is level k):
    one fewer than its levels, ROW**(len(fd) + 1) >= n."""
    d: jnp.ndarray
    p: jnp.ndarray
    lo: jnp.ndarray
    n: jnp.ndarray
    fd: Tuple[jnp.ndarray, ...] = ()
    fp: Tuple[jnp.ndarray, ...] = ()


def resident(planes: dict, lo, n, levels: int) -> Window:
    """A window of a field's resident planes (`plane_keys(levels)` of
    `Segment.device_positions`)."""
    d, p = (tuple(planes[plane_key(plane, k)] for k in range(levels))
            for plane in ("doc", "pos"))
    return Window(d[0], p[0], lo, n, d[1:], p[1:])


def whole(dA: jnp.ndarray, pA: jnp.ndarray, n=None,
          levels: Optional[int] = None) -> Window:
    """An array of pairs of its own (sentinel-padded; `n` of them real,
    all by default) as a window: its fences are made here, in the
    program."""
    return Window(dA, pA, np.int32(0),
                  np.int32(dA.shape[0]) if n is None else n,
                  fences(dA, levels), fences(pA, levels))


def anchor_window(w: Window, bucket: int):
    """The anchors: `bucket` slots of the planes that cover the window
    (bucket >= n; the planes' length where they are shorter), as (doc,
    position) with the sentinel doc in every slot outside it."""
    size = min(bucket, w.d.shape[0])
    with jax.named_scope(JOIN_SCOPE):
        start = jnp.clip(w.lo, 0, w.d.shape[0] - size).astype(jnp.int32)
        slot = start + jnp.arange(size, dtype=jnp.int32)
        inside = (slot >= w.lo) & (slot < w.lo + w.n)
        d = jax.lax.dynamic_slice(w.d, (start,), (size,))
        p = jax.lax.dynamic_slice(w.p, (start,), (size,))
        return jnp.where(inside, d, INT32_SENTINEL), jnp.where(inside, p, 0)


def window_searchsorted(w: Window, dq: jnp.ndarray,
                        pq: jnp.ndarray) -> jnp.ndarray:
    """Slot of the first pair of the window that is >= (dq, pq), `lo + n`
    where none is, vectorized over the queries (dq, pq: i32[Q]): a
    `ROW`-ary descent through the window's fence levels, the anchors walked
    `PASS` a pass."""
    q = dq.shape[0]
    if q <= PASS:
        return _descend(w, dq, pq)
    pad = -q % PASS
    passes = [jnp.pad(x, (0, pad)).reshape(-1, PASS) for x in (dq, pq)]
    return jax.lax.map(lambda qs: _descend(w, *qs),
                       tuple(passes)).reshape(-1)[:q]


def _descend(w: Window, dq: jnp.ndarray, pq: jnp.ndarray) -> jnp.ndarray:
    """`window_searchsorted` for one pass of queries. The state a level is
    `t`, the index of the level's last entry inside the window that is
    less than the key (one before the window's first entry where none is):
    the entries of the level below that can still be less lie in row `t`
    of it, and `t + 1` of the plane itself is the slot."""
    levels = ((w.d, w.p),) + tuple(zip(w.fd, w.fp))
    end = (w.lo + w.n).astype(jnp.int32)
    dq, pq = dq[:, None], pq[:, None]

    def ceil_shift(x, k):       # ceil(x / ROW**k), with no overflow
        bits = k * ROW_BITS
        return (x >> bits) + ((x & ((1 << bits) - 1)) != 0)

    def last_less(k, base, dm, pm):
        """Index of the last entry of level k, among those at indices `base`
        on (with values dm, pm), that lies inside the window and is less
        than the key: the entries inside the window are sorted and these
        hold every one that can be less, so it is a count."""
        at = base + jnp.arange(dm.shape[-1], dtype=jnp.int32)[None, :]
        first = ceil_shift(w.lo, k).astype(jnp.int32)
        inside = (at >= first) & (at < ceil_shift(end, k))
        less = inside & ((dm < dq) | ((dm == dq) & (pm < pq)))
        return jnp.maximum(base, first) \
            + jnp.sum(less, axis=-1, keepdims=True, dtype=jnp.int32) - 1

    # the top level: ROW entries or fewer lie inside the window, one slice
    # covers them and is compared against every query densely
    top = len(levels) - 1
    fd, fp = levels[top]
    size = min(ROW, fd.shape[0])
    start = jnp.clip(ceil_shift(w.lo, top), 0, fd.shape[0] - size).astype(
        jnp.int32)
    t = last_less(top, start.reshape(1, 1),
                  jax.lax.dynamic_slice(fd, (start,), (size,))[None, :],
                  jax.lax.dynamic_slice(fp, (start,), (size,))[None, :])
    # a level below: the one row under the child, a row gather a plane
    for k in range(top - 1, -1, -1):
        ld, lp = (_whole_rows(x).reshape(-1, ROW) for x in levels[k])
        row = jnp.maximum(t, 0)
        t = last_less(k, row << ROW_BITS, ld[row[:, 0]], lp[row[:, 0]])
    return t[:, 0] + 1


def nearest_delta(w: Window, d0: jnp.ndarray, base: jnp.ndarray, shift=0):
    """Signed displacement (adjusted position - base) of the term occurrence
    nearest to the anchor within the anchor's doc, and a found flag.
    `shift` is the query-position offset of this term against the anchor's:
    the planes stay RAW (device-resident per segment), adjusted position =
    p - shift."""
    last = w.d.shape[0] - 1
    idx = window_searchsorted(w, d0, base + shift)
    ridx = jnp.minimum(idx, last)
    right_ok = (idx < w.lo + w.n) & (w.d[ridx] == d0)
    right_delta = (w.p[ridx] - shift - base).astype(jnp.float32)
    right_cost = jnp.where(right_ok, right_delta, BIG_COST)
    lidx = jnp.maximum(idx - 1, 0)
    left_ok = (idx > w.lo) & (w.d[lidx] == d0)
    left_delta = (w.p[lidx] - shift - base).astype(jnp.float32)
    left_cost = jnp.where(left_ok, -left_delta, BIG_COST)
    delta = jnp.where(right_cost <= left_cost, right_delta, left_delta)
    return delta, right_ok | left_ok


def phrase_freqs(anchor_d: jnp.ndarray, anchor_p: jnp.ndarray,
                 others: List, slop: jnp.ndarray, ndocs_pad: int,
                 ordered: bool = False, gap_cost: bool = False,
                 shifts: Optional[List] = None) -> jnp.ndarray:
    """Dense per-doc sloppy phrase frequency f32[ndocs_pad].

    anchor_d/anchor_p: the anchor term's (doc, position) pairs (sentinel
    doc in every unused slot: `anchor_window`). others: the remaining
    terms, a `Window` each (a bare (d, p) pair of sorted, sentinel-padded
    arrays is read as `whole`); `shifts`: each one's query position less
    the anchor's (negative before it).

    Cost of an occurrence, compared against `slop`:
    - default (match_phrase slop): total movement against the OPTIMAL common
      offset, min_s Σ|delta_i - s| — attained at the median of the per-term
      deltas — matching Lucene SloppyPhraseMatcher's "total movement" slop
      (all terms may move, e.g. `quick and nimble brown fox` vs `quick brown
      fox` costs 2, not 4, because brown+fox stay put and quick moves).
      At slop 0 the cost is 0 only where every delta is 0: every other
      window holds (d, base + shift) itself, so whichever term anchors, the
      count is the same (`compiler.prepare` anchors an exact phrase on its
      term of fewest positions).
    - gap_cost=True (span_near slop / intervals max_gaps): positions inside
      the matched span not covered by a query term (span_width - m) — so an
      adjacent transposition costs 0 gaps but 2 moves.

    `ordered` (span_near in_order / intervals ordered) switches to a greedy
    sequential join: term i takes its EARLIEST adjusted position >= term
    i-1's (pos_i > pos_{i-1} in absolute terms). Greedy-earliest is exact for
    ordered existence anchored at each term-0 occurrence, and the resulting
    gap count is simply the last delta. Ordered implies gap cost (both its
    callers are span-family queries)."""
    others = [o if isinstance(o, Window) else whole(*o) for o in others]
    m = len(others) + 1
    if shifts is None:
        shifts = [0] * len(others)
    with jax.named_scope(JOIN_SCOPE):
        ok = anchor_d != INT32_SENTINEL
        if ordered:
            prev = jnp.zeros(anchor_p.shape, jnp.int32)  # delta_0 = 0
            for w, sh in zip(others, shifts):
                idx = window_searchsorted(w, anchor_d, anchor_p + prev + sh)
                safe = jnp.minimum(idx, w.d.shape[0] - 1)
                found = (idx < w.lo + w.n) & (w.d[safe] == anchor_d)
                prev = w.p[safe] - sh - anchor_p
                ok = ok & found
            # = pos_last - pos_0 + 1 - m = gaps
            cost = prev.astype(jnp.float32)
        elif m > 1:
            deltas = [jnp.zeros(anchor_d.shape, jnp.float32)]
            for w, sh in zip(others, shifts):
                di, found = nearest_delta(w, anchor_d, anchor_p, sh)
                ok = ok & found
                deltas.append(di)
            if gap_cost:
                # unordered gaps: span width over nearest-per-term choices — a
                # superset-leaning heuristic (exact when terms don't compete)
                abs_off = [di + jnp.float32(i) for i, di in enumerate(deltas)]
                span_hi = abs_off[0]
                span_lo = abs_off[0]
                for a in abs_off[1:]:
                    span_hi = jnp.maximum(span_hi, a)
                    span_lo = jnp.minimum(span_lo, a)
                cost = span_hi - span_lo + 1.0 - jnp.float32(m)
            else:
                stacked = jnp.sort(jnp.stack(deltas, axis=0), axis=0)
                med = stacked[m // 2]
                cost = jnp.zeros(anchor_d.shape, jnp.float32)
                for di in deltas:
                    cost = cost + jnp.abs(di - med)
        else:
            cost = jnp.zeros(anchor_d.shape, jnp.float32)
        ok = ok & (cost <= slop)
        w = jnp.where(ok, 1.0 / (1.0 + cost), 0.0)  # Lucene sloppyFreq
    with jax.named_scope(ACCUMULATE_SCOPE):
        return jnp.zeros(ndocs_pad, jnp.float32).at[anchor_d].add(
            w, mode="drop")


def phrase_score(freq: jnp.ndarray, dl: jnp.ndarray, live: jnp.ndarray,
                 weight: jnp.ndarray, k1: float, b: float,
                 avgdl: jnp.ndarray):
    """BM25 over the phrase frequency: weight = sum of the terms' idf*boost
    (Lucene PhraseWeight scores the phrase as one pseudo-term)."""
    with jax.named_scope(SCORE_SCOPE):
        k = k1 * (1.0 - b + b * dl / avgdl)
        scores = weight * freq / (freq + k)
        matched = (freq > 0) & (live > 0)
        return jnp.where(matched, scores, 0.0), matched
