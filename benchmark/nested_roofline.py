"""What a `nested` clause of the kind `nested` has to read and write, and
how long the device took over it.

`clause_bytes(answers, questions)` counts, from the data alone and
whatever the program does, the bytes one `nested` clause over a `range` on
`answers.date` has to move: 8 bytes of the date and 4 of the parent map
for every answer row, 4 written for every question (its count, or its bit
widened). A child space padded to a power of two, the mask kept as
float32, a scatter's read-modify-write of the parents' plane, a second
pass for the score: all of that is the program's form, and moves the time,
not this count. The clause compares and adds, so it is bound by memory:
bytes over `peaks.json`'s `hbm_bytes_per_s` is the least time it could
take, and that over the device's time in the stages
`executor.nested_child` and `executor.nested_join` its share of the
roofline (`nested_join_hbm_roofline_share`).

The share's bytes and its time are of the SAME requests: the trace covers
the window's first `ctx["trace"]["requests"]` requests, and only the
shapes that carry the clause pay for it. The deployment's `hold` notes the
window's shapes in the order the window sent them (`note_window`);
`query_bytes(ctx)` is the mean over the traced ones (a request without the
clause counts 0, as it spends no time in the stages), None before any were
noted.

The stages are read by `launch_reduce.stage_ms_per_query`: their names
stand under the executor's prefix (`launch_reduce.STAGE_PREFIXES`), inside
its `executor.match`; `executor.nested_inner` is the inner hits' launch,
a stage of its own that the share leaves out."""

from __future__ import annotations

CHILD = "executor.nested_child"
JOIN = "executor.nested_join"
INNER = "executor.nested_inner"
_window: dict = {}


def clause_bytes(answers: int, questions: int) -> int:
    return 12 * int(answers) + 4 * int(questions)


def note_window(answers: int, questions: int, clauses: list) -> None:
    """`clauses`: how many `nested` clauses each request of the window
    carries, in the order sent."""
    _window.update(bytes=clause_bytes(answers, questions),
                   clauses=[int(c) for c in clauses])


def query_bytes(ctx):
    traced = _window.get("clauses", [])[: int(ctx["trace"]["requests"])]
    if not traced:
        return None
    return _window["bytes"] * sum(traced) / len(traced)
