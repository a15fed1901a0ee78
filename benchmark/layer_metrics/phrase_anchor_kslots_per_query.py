"""Positions: `phrase.anchor_slots` delta / queries, in thousands: the
slots of the anchor windows the launches sliced (a phrase's term of fewest
positions, padded to a power of four; `phrase.anchor_positions` beside it
is what they held). A program without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    slots = w["counters"].get("phrase.anchor_slots")
    if slots is None or not w["queries"]:
        return None
    return slots / 1e3 / w["queries"]
