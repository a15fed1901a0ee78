"""Column executor: `aggs.terms.gathered_rows` delta / queries, in millions:
the flat values to which the window's keyword group-bys (`terms`,
`significant_terms`, a keyword `cardinality` or `value_count`) gathered the
query's match through `doc_of_value`, one element a value, counted at each
launch from the static spec and the column's device dict
(`compiler._agg_cost`, by `ops.aggs.counts_by_value`, the predicate the ops
themselves choose by). A column in which no document holds two values is
counted by document and gathers nothing: 0. The device's time there was
141-144 ms a gather of 2^24 elements on a v5e (PERF.md, PR 43). A program
without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    rows = w["counters"].get("aggs.terms.gathered_rows")
    if rows is None or not w["queries"]:
        return None
    return rows / 1e6 / w["queries"]
