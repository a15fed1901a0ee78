"""The control of the kind `vectorsearch`'s check: the reference itself,
with its products in bfloat16, in the program's place.

`bfloat16_products`: both operands of every inner product rounded to
bfloat16 and accumulated in float32: what one pass of the chip's matrix
unit computes for a float32 product that names no precision, the nearest
precision below the float32 the deployment states. Its pages (its own top
k, its own scores) held to the float64 reference by the kind's own rule
have to come out not correct, by `score_rel_err_max`; a check that lets
them pass would let a program pass that scores in bfloat16. Host numpy
only: it touches no device (`benchmark/tests/test_vectorsearch.py` keeps
it at a small size; PERF.md section 2 has the readings at the cell's).

    python3 benchmark/vectorsearch_control.py [ndocs] [queries]
"""

from __future__ import annotations

import numpy as np

import vectorsearch_reference as reference


def run(vectors: np.ndarray, specs: list, space: str, k: int, rtol: float,
        recall_floor: float, exact=None) -> dict:
    """`specs` answered by the bfloat16 reference and held to the exact
    one."""
    import ml_dtypes
    exact = exact or reference.Reference(vectors, space)
    low = reference.Reference(vectors, space,
                              product_dtype=ml_dtypes.bfloat16)
    scores = low.scores(np.stack([s["vector"] for s in specs]))
    held = [(s, reference.as_response(low.page(scores[i], k)))
            for i, s in enumerate(specs)]
    return {"bfloat16_products": reference.hold(held, exact, k, rtol,
                                                recall_floor)}


if __name__ == "__main__":
    import json
    import sys

    import run as harness
    import vectorsearch_vectors as vectors_
    loaded = harness.load_cell("cohere10m.search1.knn100")
    config, traffic = loaded["config"], loaded["traffic"]
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else int(config["ndocs"])
    corpus = vectors_.generate(ndocs, int(config["corpus_seed"]),
                               config["generator"])
    stream = harness.load_kind(config["deployment_kind"]).stream(
        {"corpus": corpus}, traffic, int(traffic["pool_seed"]))
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    g = config["guarantees"]
    out = run(corpus["vectors"], stream.take(n), config["space_type"],
              int(config["k"]), float(g["score_rtol"]),
              float(g["recall_at_k_floor"]))
    print(json.dumps(dict(out, ndocs=ndocs)))
    if any(v["correct"] for v in out.values()):
        raise SystemExit("control: came out correct")
