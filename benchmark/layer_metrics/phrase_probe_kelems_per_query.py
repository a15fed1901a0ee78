"""Positions: `phrase.probe_elems` delta / queries, in thousands: the
elements the join's binary searches gather one at a time (anchor slots x
other terms x (two a round of the search's depth, and the slot it lands
on): `ops.positions.probe_elems`, counted a launch by
`programs.count_phrase`). A program without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    elems = w["counters"].get("phrase.probe_elems")
    if elems is None or not w["queries"]:
        return None
    return elems / 1e3 / w["queries"]
