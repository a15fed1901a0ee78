"""Column executor: `aggs.span.rows` delta over `aggs.span.segment_rows`
delta, in percent: of the rows of the segments whose launches carried
aggregations, the share inside the launches' row spans (`compiler.row_span`:
what a `range` over a column whose values are in row order leaves of the
segment, from two binary searches on the host; the whole segment where no
such range stands). The block loops of `ops.aggs`' dense and product forms
visit the blocks that meet the span and no other, so
`agg_blocked_mrows_per_query` falls with this share (it counts whole
blocks, so it stands a little above it). 100 where every span is whole
(`nyctaxis.search1.analyst`: ranges over columns in no row order). A
program without either counter (one whose loops read every block) reports
nothing."""


def read(ctx):
    counters = ctx["window"]["counters"]
    rows = counters.get("aggs.span.rows")
    segment = counters.get("aggs.span.segment_rows")
    if rows is None or not segment:
        return None
    return 100.0 * rows / segment
