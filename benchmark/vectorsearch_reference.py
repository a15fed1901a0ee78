"""The plain reference of the deployment kind `vectorsearch`, and its rule.

numpy over the run's own vectors, importing nothing of the program: for a
query vector the exact product against every row, in blocks of the float32
matrix widened to float64 (so the accumulation is float64 and the
reference's own rounding is nine digits under the limit), OpenSearch's
translation of the space to a score (`innerproduct`: `ip + 1` where
`ip >= 0`, else `1 / (1 - ip)`; `l2`: `1 / (1 + d^2)`; `cosinesimil`:
`(1 + cos) / 2`, the form the program and Lucene use), and the top k by
(score descending, row ascending).

`product_dtype` is the control's handle (`vectorsearch_control.py`): the
same products with both operands rounded to that type and accumulated in
float32 (`ml_dtypes.bfloat16`: what one pass of the chip's matrix unit
computes when a float32 product names no precision), the nearest precision
below the float32 the deployment states, has to fail the rule.

The rule (`hold`): every returned hit's `_score` within `score_rtol`
(relative) of the reference's score of *that id*; the page in
non-increasing score order; k hits of k distinct ids; recall@k of each
page against the reference's top k (an id whose reference score is within
`score_rtol` of the k-th counts as found: a tie's order is the engine's
own), and the mean over the held pages at least `recall_at_k_floor`.
`hits.total` is not compared (the configuration says why)."""

from __future__ import annotations

import numpy as np

SPACES = ("innerproduct", "l2", "cosinesimil")
ROW_BLOCK = 65536


def translate(space: str, raw, vec_sq=None, q_sq=None):
    """A space's raw product (float64) as OpenSearch's score."""
    if space == "innerproduct":
        return np.where(raw >= 0, raw + 1.0, 1.0 / (1.0 - np.minimum(raw, 0)))
    if space == "cosinesimil":
        return (1.0 + raw) / 2.0
    return 1.0 / (1.0 + np.maximum(vec_sq + q_sq - 2.0 * raw, 0.0))


class Reference:
    """Scores of query vectors against `vectors` f32[n, dims]."""

    def __init__(self, vectors: np.ndarray, space: str = "innerproduct",
                 product_dtype=None):
        if space not in SPACES:
            raise ValueError(f"space {space!r} (has {SPACES})")
        self.vectors, self.space = vectors, space
        self.product_dtype = product_dtype

    def _operand(self, a: np.ndarray) -> np.ndarray:
        if self.product_dtype is None:
            return a.astype(np.float64)
        return a.astype(self.product_dtype).astype(np.float32)

    def scores(self, queries: np.ndarray) -> np.ndarray:
        """f64[nq, n]: every row's score for every query."""
        q = np.asarray(queries, np.float32).reshape(-1,
                                                    self.vectors.shape[1])
        q64 = q.astype(np.float64)
        if self.space == "cosinesimil":
            q = (q64 / np.linalg.norm(q64, axis=1, keepdims=True)
                 ).astype(np.float32)
        qt = self._operand(q).T
        q_sq = (q64 * q64).sum(axis=1)
        out = np.empty((len(q), len(self.vectors)), np.float64)
        for lo in range(0, len(self.vectors), ROW_BLOCK):
            blk = self.vectors[lo: lo + ROW_BLOCK]
            b64 = blk.astype(np.float64)
            v_sq = (b64 * b64).sum(axis=1)
            if self.space == "cosinesimil":    # rows unit length, as stored
                blk = (b64 / np.maximum(np.sqrt(v_sq), 1e-12)[:, None]
                       ).astype(np.float32)
            raw = (self._operand(blk) @ qt).astype(np.float64)
            out[:, lo: lo + len(blk)] = translate(
                self.space, raw, v_sq[:, None], q_sq[None, :]).T
        return out

    def page(self, scores: np.ndarray, k: int) -> list:
        """The top `k` of one query's scores: [(score, row)], by (score
        descending, row ascending)."""
        k = min(k, len(scores))
        head = np.argpartition(-scores, k - 1)[:k] if k < len(scores) \
            else np.arange(len(scores))
        # everything that ties the k-th belongs to the choice
        head = np.flatnonzero(scores >= scores[head].min())
        head = head[np.lexsort((head, -scores[head]))][:k]
        return [(float(scores[i]), int(i)) for i in head]


def as_response(page: list) -> dict:
    """A reference page in the response's shape (what the control is held
    by): float32 scores, as the program's are."""
    return {"hits": {"total": {"value": len(page), "relation": "eq"},
                     "hits": [{"_id": str(row),
                               "_score": float(np.float32(score)),
                               "fields": {"_id": [str(row)]}}
                              for score, row in page]}}


def hit_row(hit: dict) -> int:
    """The row a hit names: `docvalue_fields: ["_id"]` where the program
    fills it (as OSB's runner reads it), else the hit's own `_id`."""
    ids = hit.get("fields", {}).get("_id")
    return int(ids[0] if ids else hit["_id"])


def compare(resp: dict, scores: np.ndarray, k: int, rtol: float) -> dict:
    """One response against one query's reference scores -> the rule's
    counts, its largest relative score error and its recall@k."""
    out = {"error_responses": 0, "page_violations": 0, "order_violations": 0,
           "score_rel_err_max": 0.0, "recall": 0.0}
    if "error" in resp or "hits" not in resp:
        out["error_responses"] = 1
        return out
    hits = resp["hits"]["hits"]
    rows = [hit_row(h) for h in hits]
    want = min(k, len(scores))
    out["page_violations"] = abs(len(hits) - want) + len(rows) \
        - len(set(rows))
    got = np.asarray([h["_score"] for h in hits], np.float64)
    out["order_violations"] = int((np.diff(got) > 0).sum())
    if rows:
        ref = scores[rows]
        out["score_rel_err_max"] = float(
            (np.abs(got - ref) / np.abs(ref)).max())
        kth = np.partition(scores, len(scores) - want)[len(scores) - want]
        found = scores[sorted(set(rows))] >= kth * (1.0 - rtol)
        out["recall"] = float(found.sum()) / want
    return out


def hold(held: list, ref: Reference, k: int, rtol: float,
         recall_floor: float) -> dict:
    """(spec, response) pairs held to `ref` by the rule; a spec carries its
    query under `vector`. `recall_at_k_mean` stands beside its floor, every
    other number beside its ceiling."""
    worst = {"error_responses": 0, "page_violations": 0,
             "order_violations": 0, "score_rel_err_max": 0.0}
    recalls = []
    if held:
        all_scores = ref.scores(np.stack([s["vector"] for s, _r in held]))
    for i, (_spec, resp) in enumerate(held):
        one = compare(resp, all_scores[i], k, rtol)
        recalls.append(one.pop("recall"))
        worst["score_rel_err_max"] = max(worst["score_rel_err_max"],
                                         one.pop("score_rel_err_max"))
        for name, v in one.items():
            worst[name] += v
    mean = float(np.mean(recalls)) if recalls else 0.0
    numbers = {"score_rel_err_max": [worst["score_rel_err_max"], rtol],
               "order_violations": [worst["order_violations"], 0],
               "page_violations": [worst["page_violations"], 0],
               "error_responses": [worst["error_responses"], 0],
               "recall_at_k_mean": [mean, recall_floor]}
    return {"compared": len(held), "numbers": numbers,
            "recall_at_k_min": min(recalls, default=0.0),
            "correct": bool(held) and mean >= recall_floor and all(
                v <= limit for name, (v, limit) in numbers.items()
                if name != "recall_at_k_mean")}
