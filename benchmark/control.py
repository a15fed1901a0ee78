#!/usr/bin/env python3
"""The control of the check: the reference in the program's place, computed
in the nearest precision below the float32 the configurations state
(bfloat16 planes and accumulation), held to the float32 reference by the
same rule at the cell's own size. It has to come out NOT correct.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 [--queries 24]

Host numpy only: it needs no chip and touches no JAX device. One JSON line a
seed with the numbers compared beside their limits, then a last line with
the smallest `score_rel_err_max` over the seeds, which is the upper end the
limit has to stay under (PERF.md section 2 has the readings)."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_numbers(config: dict, traffic: dict, seed: int, nq: int) -> dict:
    """Pages of the bfloat16 reference held to the float32 reference."""
    import ml_dtypes

    import corpus
    import queries
    import reference
    starts, doc_ids, tfs, dl, df = corpus.from_config(config)
    g, size = config["guarantees"], int(traffic["size"])
    csr = (starts, doc_ids, tfs)
    ref = reference.Reference(csr, dl, g["bm25_k1"], g["bm25_b"])
    low = reference.Reference(csr, dl, g["bm25_k1"], g["bm25_b"],
                              dtype=ml_dtypes.bfloat16)
    stream = queries.QueryStream(df, corpus.vocab_strings(len(df)), seed,
                                 traffic)
    pairs = []
    for spec in stream.take(nq):
        p = low.page(spec, size)
        pairs.append((spec, {"hits": {
            "total": {"value": p["total"], "relation": p["relation"]},
            "hits": [{"_id": i, "_score": s}
                     for i, s in zip(p["ids"], p["scores"])]}}))
    out = reference.hold(pairs, ref, size, int(g["page"]),
                         float(g["score_rtol"]))
    out.pop("first_failures")
    return out


def main(argv=None) -> None:
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--queries", type=int, default=24)
    args = ap.parse_args(argv)
    loaded = run.load_cell(args.workload)
    worst = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_numbers(loaded["config"], loaded["traffic"], seed,
                              args.queries)
        print(json.dumps(dict(out, control="bfloat16", seed=seed,
                              workload=args.workload)), flush=True)
        worst.append(out["numbers"]["score_rel_err_max"][0])
        if out["correct"]:
            raise SystemExit(f"control: seed {seed} came out correct")
    print(json.dumps({"control": "bfloat16", "workload": args.workload,
                      "score_rel_err_max_smallest": min(worst),
                      "limit": loaded["config"]["guarantees"]["score_rtol"]}))


if __name__ == "__main__":
    main()
