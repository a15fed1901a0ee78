"""The control of the kind `nyc_taxis`'s check: the reference itself, twice
weakened, in the program's place.

(1) `float32_sums`: a bucket's `sum` accumulated one value after another
in one float32, the nearest precision below the float64 the rule states.
Past 2^24 times its addends' size such an accumulator rounds every addend
to its own spacing: at the cell's size the bucket [1, 2) miles holds some
8M fares near 10 and sums to 8e7, where the spacing is 8. (2)
`column_span`: `auto_date_histogram`'s rounding taken from the column's
span (a year of drop-offs: months) and not from the matched documents' (a
fortnight: days). Held to the exact reference by the kind's own rule each
has to come out not correct, (1) by `sum_rel_err_max`, (2) by
`interval_mismatches` and `bucket_mismatches`; a check that lets them pass
would let a program pass that sums in float32 or bins by the column. Host
numpy only: it touches no device (`benchmark/tests/test_nyc_taxis.py`
keeps it at a small size; PERF.md section 2 has the readings at the
cell's).

    python3 benchmark/nyc_taxis_control.py [ndocs] [requests]
"""

from __future__ import annotations

import numpy as np

import nyc_taxis_reference as reference

CONTROLS = {"float32_sums": {"sum_dtype": np.float32},
            "column_span": {"interval_from": "column"}}


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

    import nyc_taxis_trips as trips
    import run as harness
    loaded = harness.load_cell("nyctaxis.search1.analyst")
    config, traffic = loaded["config"], loaded["traffic"]
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else int(config["ndocs"])
    columns = trips.generate(ndocs, int(config["corpus_seed"]),
                             config["generator"])
    stream = harness.load_kind(config["deployment_kind"]).stream(
        {"columns": columns}, traffic, int(traffic["pool_seed"]))
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    print(json.dumps(dict(run(columns, stream.take(n)), ndocs=ndocs)))
