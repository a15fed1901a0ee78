"""Positions: `phrase.probe_rows` delta over `phrase.probe_elems` delta, in
percent: of the indices the phrase join gathers one at a time
(`ops.positions.probe_elems`), the share that fetch a whole row of the
resident planes or of their fence levels, a level of the search's descent
below its top, and not a single element (`ops.positions.probe_rows`; both
counted a launch by `programs.count_phrase` from the static spec). A gather
costs this chip by the index, so a search whose probes are rows reads its
window in fewer of them: the share rises as `phrase_probe_kelems_per_query`
falls. A program without either counter (one whose search reads an element
a probe has no `probe_rows`) reports nothing."""


def read(ctx):
    counters = ctx["window"]["counters"]
    rows = counters.get("phrase.probe_rows")
    elems = counters.get("phrase.probe_elems")
    if rows is None or not elems:
        return None
    return 100.0 * rows / elems
