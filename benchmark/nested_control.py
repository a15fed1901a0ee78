"""The control of the kind `nested`'s check: the reference itself, four
times weakened, in the program's place.

(a) `any_answer`: the child clause dropped, so every question with an
answer matches: what a "has any child" bit can say. It fails by
`total_mismatches` and `rank_mismatches` (and by `length_mismatches` where
the exact answer does not fill a page). (b) `all_answers`: inner hits
taken as a hit's whole block, not its matching answers: it fails by
`inner_total_mismatches` alone. (c) `sort_min`: the nested sort's `max`
taken as `min`: it fails by `sort_value_mismatches` alone. (d)
`bfloat16_score`: the join in bfloat16, the nearest precision below the
float32 the configuration states. A question's count of matching answers
accumulated in bfloat16 and compared with 0 is still exact, so this one
weakens the score, `idf / (1 + k1)` computed in bfloat16: it fails by
`score_rel_err_max` alone. Held to the exact reference by the kind's own
rule each has to come out not correct, by the limit named; a check that
lets them pass would let a program pass that joins no block, shows answers
that do not match, sorts by the wrong end or scores in half precision.
Host numpy only: it touches no device (`benchmark/tests/test_nested.py`
keeps it at a small size over two corpus seeds; PERF.md section 2 has the
readings at the cell's).

    python3 benchmark/nested_control.py [ndocs] [requests]
"""

from __future__ import annotations

import nested_reference as reference

CONTROLS = {"any_answer": ("total_mismatches", "rank_mismatches"),
            "all_answers": ("inner_total_mismatches",),
            "sort_min": ("sort_value_mismatches",),
            "bfloat16_score": ("score_rel_err_max",)}


def _weakened(q: dict, how: str, live=None) -> reference.Reference:
    if how == "bfloat16_score":
        import ml_dtypes
        return reference.Reference(q, live, score_dtype=ml_dtypes.bfloat16)
    return reference.Reference(q, live, **{how: True})


def run(q: dict, specs: list, exact=None, live=None) -> dict:
    """control name -> `specs` answered by that weakened reference and held
    to the exact one."""
    exact = exact or reference.Reference(q, live)
    out = {}
    for how in CONTROLS:
        low = _weakened(q, how, live)
        held = [(s, reference.as_response(low.answer(s), s)) for s in specs]
        out[how] = reference.hold(held, exact)
    return out


if __name__ == "__main__":
    import json
    import sys

    import nested_questions as questions
    import run as harness
    loaded = harness.load_cell("nested.search1.answers")
    config, traffic = loaded["config"], loaded["traffic"]
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else int(config["ndocs"])
    q = questions.generate(ndocs, int(config["corpus_seed"]),
                           config["generator"])
    stream = harness.load_kind(config["deployment_kind"]).stream(
        {"questions": q}, traffic, int(traffic["pool_seed"]))
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 30
    out = run(q, stream.take(n))
    print(json.dumps({how: dict(r["numbers"], correct=r["correct"])
                      for how, r in out.items()} | {"ndocs": ndocs}))
