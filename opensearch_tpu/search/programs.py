"""Program assembly: the one module that sees a whole request. Builds and
launches `executor_program`, `agg_program` and `auto_range_program` from
the query, sort and aggregation specs, and counts what each launch costs.

Top of the five modules `compiler.py` pictures: imports `agg_compiler`,
`compiler`, `aggregations` (its counter group) and `ops/`.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ..ops import aggs as agg_ops
from ..ops import scoring as ops
from ..utils.trace import TRACER
from .agg_compiler import emit_agg
from .aggregations import AGG_STATS
from .compiler import (EXECUTOR_STATS, KNN_STATS, NESTED_STATS,
                       PHRASE_STATS, ROW_SPAN,
                       canon_param_key, canon_spec, emit, emit_sort_key,
                       instrumented_program_cache)


def launch_span(params: dict) -> Optional[Tuple]:
    """(lo, hi) of the row span a launch's `params` carry
    (`compiler.bind_row_span`: traced scalars inside a program, numpy's at
    the call), None where they carry none: the whole segment."""
    span = params.get(ROW_SPAN)
    return None if span is None else (span[0], span[1])


@instrumented_program_cache("executor", maxsize=512)
def _build_executor(full_spec):
    import jax

    # a miss of the program cache: `phrase.programs` counts those of a spec
    # with a `phrase` node (the phrase shapes met so far)
    if next(_nodes_of(("phrase",), full_spec[0]), None) is not None:
        PHRASE_STATS.inc("programs")
    # and `nested.programs` those of a spec with a `nested` node
    if next(_nodes_of(("nested",), full_spec[0]), None) is not None:
        NESTED_STATS.inc("programs")
    return jax.jit(_executor_run_fn(full_spec))


def _executor_run_fn(full_spec):
    """The raw (unjitted) per-segment executor body, jitted by
    `_build_executor` — the ONE program both the direct path and the
    coalesced knn batch (`launch_segment_batch`) invoke, which is what
    makes a batched page byte-identical to its direct sibling."""
    import jax

    (query_spec, sort_spec, agg_specs, k_pad, named_specs, has_after,
     collapse_spec) = full_spec

    def executor_program(seg_arrays, params):
        import jax.numpy as jnp

        # the stages carry `jax.named_scope`s (metadata of the ops, read
        # from a trace by `benchmark/launch_reduce.py`): `executor.match`,
        # `.sort_key`, `.topk`, `.total`, `.aggs` (the forms of
        # `ops/aggs.py` name themselves inside it), `.named`
        with jax.named_scope("executor.match"):
            sm = emit(query_spec, seg_arrays, params)
        live = seg_arrays["live"]
        with jax.named_scope("executor.sort_key"):
            key = emit_sort_key(sort_spec, seg_arrays, params, sm.scores)
            matched = sm.matched
            if has_after:
                # search_after: strictly below the cursor in ranking order
                matched = matched & (key < params["after_key"])
            sm = ops.ScoredMask(sm.scores, matched.astype(jnp.float32))
        with jax.named_scope("executor.topk"):
            if collapse_spec is not None:
                _, cfield, n_ord_pad, use_kw = collapse_spec
                if use_kw:
                    ords = seg_arrays["keyword"][cfield]["min_ord"]
                else:
                    ords = params["collapse_ords"]
                vals, idx = ops.collapse_topk(key, sm.matched, live, ords,
                                              n_ord_pad, k_pad)
            else:
                vals, idx = ops.topk_docs(key, sm.matched, live, k_pad)
            topk_scores = sm.scores[idx]
        with jax.named_scope("executor.total"):
            total = ops.total_hits(sm.matched, live)
            max_score = jnp.max(jnp.where(sm.matched & (live > 0),
                                          sm.scores, -jnp.inf))
        out = {
            "topk_key": vals,
            "topk_idx": idx,
            "topk_scores": topk_scores,
            "total": total,
            "max_score": max_score,
        }
        aggs = {}
        with jax.named_scope("executor.aggs"):
            match_f = (sm.matched.astype(jnp.float32)
                       * jnp.where(live > 0, 1.0, 0.0))
            for name, aspec in agg_specs:
                res = emit_agg(aspec, seg_arrays, params, match_f, sm.scores,
                               launch_span(params))
                if res:  # oslint: disable=OSL201 -- host dict truthiness, trace-static
                    aggs[name] = res
        if aggs:  # oslint: disable=OSL201 -- host dict truthiness, trace-static
            out["aggs"] = aggs
        named = {}
        with jax.named_scope("executor.named"):
            for nm, nspec in named_specs:
                nsm = emit(nspec, seg_arrays, params)
                named[nm] = nsm.matched[idx]
        if named:  # oslint: disable=OSL201 -- host dict truthiness, trace-static
            out["named"] = named
        return out

    return executor_program


def launch_segment_batch(prepared: list, seg_arrays: dict):
    """LAUNCH a coalesced batch of per-query executor programs over one
    segment: every query's invocation of THE direct-path program
    (`_build_executor`, shared jit cache — structurally identical
    queries compile once) enqueues here UNFETCHED; the returned closure
    performs one deferred `device_get` sweep for the whole batch
    (oslint OSL504). `prepared` is a list of `(full_spec, params)`
    already canonicalized via `canon_query`.

    Deliberately NOT a vmapped mega-program: vmap's batched dot_general
    lands ~1 ULP away from the scalar program's contraction on real
    backends, and a scheduler-coalesced page must stay BYTE-identical
    to its scheduler-off sibling (the f32 single-domain serving
    contract, docs/FASTPATH.md) — the batching win here is cross-request
    coalescing + async launch pipelining, with the score domain pinned
    by construction."""
    import jax

    pending = []
    for full_spec, cparams in prepared:
        exe = _build_executor(full_spec)
        _count_launch(full_spec, seg_arrays, cparams)
        pending.append(exe(seg_arrays, cparams))   # invocation, no sync

    def _fetch():
        with TRACER.span("device.wait", program="executor"):
            return jax.device_get(pending)

    return _fetch


def _count_launch(full_spec, seg_arrays: dict, cparams: dict) -> None:
    """One launch of `executor_program`, counted. `executor.params_h2d_bytes`:
    the bytes of every host numpy array or scalar it is handed (each is
    copied to the device by the call; planes that live there are not
    counted). `executor.topk_keys_sorted`: the keys its `ops.topk_docs`
    hands to `lax.top_k` (a collapse launch takes `collapse_topk`: none).
    `executor.agg_bucket_launches` / `agg_run_counted`: its date-histogram
    bucket counts, and those whose spec says "runs". `aggs.span.rows` /
    `aggs.span.segment_rows`: of a launch that carries aggregations, the
    rows of its span (`launch_span`: the segment's where it carries none)
    and the segment's, both of the padded planes."""
    EXECUTOR_STATS.inc("params_h2d_bytes", sum(
        v.nbytes for v in cparams.values()
        if isinstance(v, (np.ndarray, np.generic))))
    _query, _sort, aggs, k_pad, _named, _after, collapse_spec = full_spec
    if collapse_spec is None:
        EXECUTOR_STATS.inc("topk_keys_sorted", ops.topk_keys_sorted(
            seg_arrays["live"].shape[0], k_pad))
    EXECUTOR_STATS.inc("launches")
    for node in _nodes_of(("knn", "phrase"), _query):
        if node[0] == "knn":
            count_knn(node, seg_arrays, cparams)
        else:
            count_phrase(node, cparams)
    forms = list(_date_count_forms(aggs))
    if forms:
        EXECUTOR_STATS.inc("agg_bucket_launches", len(forms))
        EXECUTOR_STATS.inc("agg_run_counted", forms.count("runs"))
    if aggs:
        cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0,
                "ordinals": 0, "combinations": 0, "gathered": 0}
        span = launch_span(cparams)
        for _name, aspec in aggs:
            agg_cost(aspec, seg_arrays, cost, span)
        n = seg_arrays["live"].shape[0]
        AGG_STATS.inc("span.segment_rows", n)
        AGG_STATS.inc("span.rows", n if span is None
                      else max(int(span[1]) - int(span[0]), 0))
        if cost["ordinals"]:
            AGG_STATS.inc("terms.ordinals", cost["ordinals"])
        if cost["combinations"]:
            AGG_STATS.inc("composite.combinations", cost["combinations"])
        if cost["gathered"]:
            AGG_STATS.inc("terms.gathered_rows", cost["gathered"])
        if cost["scatter"]:
            AGG_STATS.inc("scatter.updates", cost["scatter"])
        if cost["blocked"]:
            AGG_STATS.inc("blocked.rows", cost["blocked"])
        if cost["sub_buckets"]:
            AGG_STATS.inc("bucketed_sub.launches")
            AGG_STATS.inc("bucketed_sub.buckets", cost["sub_buckets"])


def _nodes_of(kinds: tuple, spec):
    """The nodes of a query spec whose kind is among `kinds`, its filters'
    included."""
    if isinstance(spec, (tuple, list)):
        if spec and isinstance(spec[0], str) and spec[0] in kinds:
            yield spec
        for part in spec:
            yield from _nodes_of(kinds, part)


def count_phrase(node, cparams: dict) -> None:
    """One `phrase` node of a launch into `PHRASE_STATS`: what its static
    shape makes the join read, and what the request's windows hold."""
    from ..ops.positions import probe_elems, probe_rows
    _, nid, _field, m_terms, (bucket, levels) = node[:5]
    lens = cparams[f"q{nid}_len"]
    PHRASE_STATS.inc("queries")
    PHRASE_STATS.inc("anchor_slots", bucket)
    PHRASE_STATS.inc("anchor_positions", int(lens[0]))
    PHRASE_STATS.inc("window_positions", int(lens.sum()))
    PHRASE_STATS.inc("probe_elems", probe_elems(bucket, m_terms - 1, levels))
    PHRASE_STATS.inc("probe_rows", probe_rows(bucket, m_terms - 1, levels))


def count_knn(node, seg_arrays: dict, cparams: dict) -> None:
    """One `knn` node of a launch into `KNN_STATS`, by the predicate
    `emit` itself routes by."""
    _, nid, field, col_exists, _sim, _fspec, probe = node
    if not col_exists:
        return
    vc = seg_arrays["vector"][field]
    KNN_STATS.inc("queries")
    KNN_STATS.inc("query_vector_bytes", cparams[f"q{nid}_vec"].nbytes)
    if probe is not None and "ivf_centroids" in vc:
        nprobe, cap = probe
        KNN_STATS.inc("ann_queries")
        KNN_STATS.inc("lists_probed", nprobe)
        KNN_STATS.inc("candidate_slots", nprobe * cap)
    else:
        KNN_STATS.inc("exact_queries")


# where the sub-aggregation specs sit in the containers that hand their
# children this segment's own rows (the nested and join kinds hand them
# another segment's: not walked)
_AGG_CONTAINER_SUBS = {"filter": 3, "filters": 3, "global": 2, "missing": 4,
                       "range": 5, "geo_range": 5, "sampler": 4,
                       "adjacency": 4}


def agg_cost(spec, seg_arrays: dict, cost: dict, span=None) -> None:
    """What `emit_agg` builds for `spec`, reckoned from the spec and the
    launch's row span (the walk mirrors it): rows handed to scatters, rows
    read by `run_counts` and by the dense and product forms
    (`ops.aggs.count_form`, the predicate the emit chooses by: the rows of
    the blocks their loops visit under `span`, `ops.aggs.span_rows`, where
    `emit_agg` hands it down), buckets that carry a metric
    sub-aggregation, and
    where `cost` has the keys the slots a terms-like group-by counts into
    (`ordinals`; `combinations` those of a composite) and the flat values
    a keyword group-by gathers the match to (`gathered`: its rows where
    the column is laid out by value, `ops.aggs.counts_by_value`). A keyword
    `cardinality` is the `terms_counts` under its registers. Kinds that
    reduce nothing per row of the segment add nothing."""
    if not isinstance(spec, tuple) or not spec:
        return
    kind = spec[0]
    n = seg_arrays["live"].shape[0]
    rows = nb = None                    # of this node's own bucket count
    slots = 0                           # of a terms-like group-by
    if kind == "hist":
        rows, nb, subs = n, spec[6], spec[7]
    elif kind == "date_hist":
        rows, nb, subs = n, spec[7], spec[8]
    elif kind == "auto_date_hist":
        rows, nb, subs = n, spec[7], spec[8]
    elif kind in ("terms", "sig_terms", "composite_mv", "card_kw",
                  "vc_keyword"):
        kw = seg_arrays["keyword"][spec[2]]
        rows = agg_ops.group_by_rows(kw)
        if agg_ops.counts_by_value(kw):     # its rows are no documents
            span = None
            if "gathered" in cost:
                cost["gathered"] += rows
        if kind == "vc_keyword":        # one sum: no bucket count
            return
        nb = spec[3]
        if kind == "card_kw":   # `terms_counts` under the registers
            subs = ()
        else:
            subs, slots = spec[4], nb
    elif kind == "geo_grid":
        rows, nb, subs = n, spec[5], spec[6]
    elif kind == "composite":
        rows, nb, subs = n, spec[3], spec[4]
        slots = nb
        if "combinations" in cost:
            cost["combinations"] += nb
    elif kind == "multi_terms":
        rows, nb, subs = n, spec[2], spec[4]
        slots = nb
    if slots and "ordinals" in cost:    # (a caller that wants them asks)
        cost["ordinals"] += slots
    if rows is None:
        at = _AGG_CONTAINER_SUBS.get(kind)
        for sub in (spec[at] if at is not None else ()):
            agg_cost(sub, seg_arrays, cost, None if kind == "global" else span)
        return
    form = agg_ops.count_form(nb)
    # the rows of the blocks a count's loop visits, by its form
    counted = agg_ops.span_rows(span, (
        agg_ops.dense_block_rows if form == "dense"
        else agg_ops.product_block_rows)(rows), rows)
    if spec[-1] == "runs":
        cost["blocked"] += rows
    elif form != "scatter":
        cost["blocked"] += counted
    else:
        cost["scatter"] += rows
    for sub in subs:
        if sub and sub[0] == "stats" and sub[3]:
            if form == "dense":     # all of it, in the sums' blocks
                cost["blocked"] += agg_ops.span_rows(
                    span, agg_ops.sum_limb_plan(rows, nb)[2], rows)
            elif form == "product":     # its count
                cost["blocked"] += counted
            if form != "dense":
                cost["scatter"] += rows * agg_ops.sub_metric_scatters(
                    rows, nb, sub[4])
            cost["sub_buckets"] += nb


def _date_count_forms(spec):
    """The `form` of every `date_hist` / `auto_date_hist` spec in a tree of
    aggregation specs (a pair that only carries such a name, an aggregation
    a user called so, ends in no form)."""
    if isinstance(spec, tuple):
        if (spec and spec[0] in ("date_hist", "auto_date_hist")
                and spec[-1] in ("runs", "scatter")):
            yield spec[-1]
        for x in spec:
            yield from _date_count_forms(x)


def canon_query(query_spec, sort_spec, k_pad: int, params: dict):
    """Canonicalize one prepared (query, sort, k_pad) triple + params the
    way `run_segment` does — the grouping key for batched launches."""
    mapping: Dict[int, int] = {}
    full = canon_spec((query_spec, sort_spec, (), k_pad, (), False,
                       None), mapping)
    return full, {canon_param_key(k, mapping): v
                  for k, v in params.items()}


def run_segment(query_spec, sort_spec, agg_specs, named_specs, k_pad: int,
                seg_arrays: dict, params: dict, has_after: bool = False,
                collapse_spec=None) -> dict:
    # canonicalize node ids (nids come from a global counter) so
    # structurally identical queries hit the same compiled executor instead
    # of recompiling per request — the XLA analog of Lucene's per-shape
    # query plan reuse
    mapping: Dict[int, int] = {}
    full = canon_spec((query_spec, sort_spec, tuple(agg_specs), k_pad,
                       tuple(named_specs), has_after, collapse_spec),
                      mapping)
    cparams = {canon_param_key(k, mapping): v for k, v in params.items()}
    exe = _build_executor(full)
    _count_launch(full, seg_arrays, cparams)
    return exe(seg_arrays, cparams)


@instrumented_program_cache("agg", maxsize=128)
def _build_agg_executor(key):
    """Aggs-only program (no top-k): the shard-wide sampler re-threshold
    pass re-runs just the agg tree with a global threshold param."""
    import jax

    query_spec, agg_spec = key

    def agg_program(seg_arrays, params):
        import jax.numpy as jnp

        with jax.named_scope("executor.match"):
            sm = emit(query_spec, seg_arrays, params)
        live = seg_arrays["live"]
        with jax.named_scope("executor.aggs"):
            match_f = (sm.matched.astype(jnp.float32)
                       * jnp.where(live > 0, 1.0, 0.0))
            return emit_agg(agg_spec, seg_arrays, params, match_f,
                            sm.scores, launch_span(params))

    return jax.jit(agg_program)


def run_agg_only(query_spec, agg_spec, seg_arrays: dict, params: dict):
    mapping: Dict[int, int] = {}
    canon = canon_spec((query_spec, agg_spec), mapping)
    cparams = {canon_param_key(k, mapping): v for k, v in params.items()}
    return _build_agg_executor(canon)(seg_arrays, cparams)


@instrumented_program_cache("agg", maxsize=128)
def _build_auto_range_executor(key):
    """The launch an `auto_date_histogram` takes first: the least and the
    greatest value of each of `fields` among the matched live documents,
    exact in the (hi, lo) words of the int64 planes, with their count."""
    import jax

    query_spec, fields = key
    big = np.int32((1 << 31) - 1)

    def auto_range_program(seg_arrays, params):
        import jax.numpy as jnp

        with jax.named_scope("executor.match"):
            sm = emit(query_spec, seg_arrays, params)
        out = {}
        with jax.named_scope("aggs.auto_range"):
            ok0 = (sm.matched > 0) & (seg_arrays["live"] > 0)
            for f in fields:
                col = seg_arrays["numeric"][f]
                ok, hi, lo = ok0 & col["present"], col["hi"], col["lo"]
                min_hi = jnp.min(jnp.where(ok, hi, big))
                max_hi = jnp.max(jnp.where(ok, hi, -big - 1))
                out[f] = (
                    min_hi,
                    jnp.min(jnp.where(ok & (hi == min_hi), lo, big)),
                    max_hi,
                    jnp.max(jnp.where(ok & (hi == max_hi), lo, -big - 1)),
                    jnp.sum(ok.astype(jnp.int32)))
        return out

    return jax.jit(auto_range_program)


def auto_date_range(query_spec, fields: Tuple[str, ...], seg_arrays: dict,
                    params: dict) -> Dict[str, Optional[Tuple[int, int]]]:
    """field -> (least, greatest) epoch ms among the documents `query_spec`
    matches in this segment, None where none has a value: one launch and
    one read, counted as `executor.launches` and
    `aggs.auto_date.refine_launches`."""
    import jax

    mapping: Dict[int, int] = {}
    canon = canon_spec((query_spec, tuple(fields)), mapping)
    cparams = {canon_param_key(k, mapping): v for k, v in params.items()}
    EXECUTOR_STATS.inc("launches")
    AGG_STATS.inc("auto_date.refine_launches")
    out = _build_auto_range_executor(canon)(seg_arrays, cparams)
    with TRACER.span("device.wait", program="agg", outputs="auto_range"):
        got = jax.device_get(out)

    def i64(hi, lo):
        return (int(hi) << 32) + int(lo) + (1 << 31)
    return {f: (i64(v[0], v[1]), i64(v[2], v[3])) if int(v[4]) else None
            for f, v in got.items()}
