"""The control of the kind `http_logs`'s check: the reference itself with
the timestamps and the request's bounds in float32, in the program's place.

float32 is the nearest precision below what the deployment states (exact
int64 milliseconds); near 9e11 ms its spacing is 65,536 ms, so an event
moves by up to 33 s, across a window's bounds and an hour's edge. Held to
the int64 reference by the kind's own rule it has to come out not correct,
by the totals, the buckets and the sort values at once; a check that lets
it pass would let a program that keeps dates in float32 pass. Host numpy
only: it touches no device (`benchmark/tests/test_http_logs.py` keeps it
at 20,000 events; PERF.md section 2 has the reading at the cell's size).

    python3 benchmark/http_logs_control.py [ndocs] [requests]
"""

from __future__ import annotations

import numpy as np

import http_logs_reference as reference


def run(columns: dict, specs: list) -> dict:
    """`specs` answered in float32 and held to the int64 reference."""
    exact = reference.Reference(columns["ts_ms"], columns["status"],
                                columns["size"])
    low = reference.Reference(columns["ts_ms"], columns["status"],
                              columns["size"], time_dtype=np.float32)
    held = [(s, reference.as_response(low.answer(s), int(s["page"])))
            for s in specs]
    return reference.hold(held, exact)


if __name__ == "__main__":
    import json
    import sys

    import http_logs_events as events
    import run as harness
    loaded = harness.load_cell("httplogs.search1.dashboard")
    config, traffic = loaded["config"], loaded["traffic"]
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else int(config["ndocs"])
    columns = events.generate(ndocs, int(config["corpus_seed"]),
                              config["generator"])
    stream = harness.load_kind(config["deployment_kind"]).stream(
        {"columns": columns}, traffic, int(traffic["pool_seed"]))
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    print(json.dumps(dict(run(columns, stream.take(n)), ndocs=ndocs)))
