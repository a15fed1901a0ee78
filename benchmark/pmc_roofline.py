"""What an exact phrase of `pmc` has to read, and how long the device took
over it.

`pmc_reference.Reference.occurrence_bytes(terms)` counts, from the phrase's
own statistics in the reference's token stream and whatever the program
does, the bytes one `match_phrase` has to move from HBM: 4 for every
posting of its rarest term (the documents that can hold the phrase at all)
and 4 for every position of its two rarest terms inside the documents that
hold every term of the phrase (what a document-at-a-time matcher reads once
the conjunction is known). Windows padded to a power of four, a binary
search a slot where a merge would do, the commoner terms' positions, the
scatter's read-modify-write of the document plane: all of that is the
program's form, and moves the time, not this count. The join compares and
adds a four-byte element, so it is bound by memory: bytes over
`peaks.json`'s `hbm_bytes_per_s` is the least time it could take, and that
over the device's time in the stages `executor.phrase_join` and
`executor.phrase_accumulate` its share of the roofline
(`phrase_join_hbm_roofline_share`).

The share's bytes and its time are of the SAME requests: the trace covers
the window's first `ctx["trace"]["requests"]` requests, and a request's
cost spreads over two orders of magnitude, so a mean over any other set
would move the share by a multiple. The deployment's `hold` notes the
reference and the window's phrases in the order the window sent them
(`note_window`); `query_bytes(ctx)` is the mean over the traced ones,
computed when a traced run asks (one more pass of the reference over the
token stream), None before any were noted.

The stages are read by `launch_reduce.stage_ms_per_query`: their names
stand under the executor's prefix (`launch_reduce.STAGE_PREFIXES`), inside
its `executor.match`."""

from __future__ import annotations

JOIN = "executor.phrase_join"
ACCUMULATE = "executor.phrase_accumulate"
SCORE = "executor.phrase_score"
_window: dict = {}


def note_window(reference, phrases: list) -> None:
    """`phrases`: the terms of every request of the window, in the order
    sent; `reference`: what counts their bytes."""
    _window.update(reference=reference, phrases=[tuple(p) for p in phrases],
                   bytes={})


def query_bytes(ctx):
    traced = _window.get("phrases", [])[: int(ctx["trace"]["requests"])]
    if not traced:
        return None
    ref, known = _window["reference"], _window["bytes"]
    ref.learn([p for p in traced if p not in known])
    for p in traced:
        if p not in known:
            known[p] = ref.occurrence_bytes(p)
    return sum(known[p] for p in traced) / len(traced)
