"""Vector search: `knn.candidate_slots` delta / queries, in thousands: the
rows an IVF probe gathers, scores and scatters back, `nprobe * cap` a query
whatever the lists hold (padding slots included), counted at each launch
from the static spec (`compiler._count_knn`). A program without the counter
reports nothing."""


def read(ctx):
    w = ctx["window"]
    slots = w["counters"].get("knn.candidate_slots")
    if slots is None or not w["queries"]:
        return None
    return slots / 1e3 / w["queries"]
