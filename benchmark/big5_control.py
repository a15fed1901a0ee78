"""The control of the kind `big5`'s check: the reference itself, twice
weakened, in the program's place.

(1) `float16_counts`: a bucket's count accumulated one document after
another in float16, the nearest precision below the integers the rule
states: such an accumulator stops at 2,048 (2,048 + 1 rounds back to
2,048), and at the cell's size a busy log stream holds hundreds of
thousands of a window's events. (2) `top_before_mask`: the top `size`
buckets of `terms` / `multi_terms` chosen by the whole column's counts and
only then counted under the request's range: the buckets that lead the
whole fortnight are not the ones that lead a drawn window. Held to the
exact reference by the kind's own rule each has to come out not correct,
both by `bucket_mismatches` ((1) leaves `sum_other_doc_count` whole:
the buckets that stall are the ones shown); a check that lets them pass would let a program pass
that counts in half precision or ranks before it filters. Host numpy only:
it touches no device (`benchmark/tests/test_big5.py` keeps it at a small
size; PERF.md section 2 has the readings at the cell's).

    python3 benchmark/big5_control.py [ndocs] [requests]
"""

from __future__ import annotations

import numpy as np

import big5_reference as reference

CONTROLS = {"float16_counts": {"count_dtype": np.float16},
            "top_before_mask": {"top_before_mask": True}}


def run(columns: dict, specs: list, exact=None) -> dict:
    """control name -> `specs` answered by that weakened reference and held
    to the exact one."""
    exact = exact or reference.Reference(columns)
    out = {}
    for name, how in CONTROLS.items():
        low = reference.Reference(columns, **how)
        held = [(s, reference.as_response(low.answer(s), s)) for s in specs]
        out[name] = reference.hold(held, exact)
    return out


if __name__ == "__main__":
    import json
    import sys

    import big5_events as events
    import run as harness
    loaded = harness.load_cell("big5.search1.terms")
    config, traffic = loaded["config"], loaded["traffic"]
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else int(config["ndocs"])
    columns = events.generate(ndocs, int(config["corpus_seed"]),
                              config["generator"])
    stream = harness.load_kind(config["deployment_kind"]).stream(
        {"columns": columns}, traffic, int(traffic["pool_seed"]))
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 14
    print(json.dumps(dict(run(columns, stream.take(n)), ndocs=ndocs)))
