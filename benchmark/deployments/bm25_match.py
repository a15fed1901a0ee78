"""The deployment kind `bm25_match`: batch-1 or batched BM25 `match` over a
synthetic text collection (both configurations of PR 24; a configuration
without `deployment_kind` is of this kind).

The data is `corpus.py`'s Zipf text CSR from the configuration's
`corpus_seed` and `generator`, planted as one segment with `status` /
`price` columns that follow `--seed`; the device structure is the aligned
planes of `body`. The stream is `queries.QueryStream` (a traffic file's
`generator` gives term ids, the body is a `match` on `body`); a query's twin
is the same terms rotated by one. The reference is `reference.py`'s numpy
dense BM25 in float32 and its rule (top-10 scores and ranks, totals); the
limits are the configuration's `guarantees` (PERF.md section 2 has the
readings they stand between; `control.py` is the control). The counters are
the serving ladder's and the device rescore's."""

from __future__ import annotations

import time

import numpy as np

import corpus
import queries
import reference


def build(config: dict, seed: int, client, index: str) -> dict:
    """The configuration's corpus on the host (from its `corpus_seed`: the
    collection is fixed, like a data set), columns from `seed`, planted as
    a segment, promoted to HBM."""
    import jax

    from opensearch_tpu.search import fastpath

    t0 = time.time()
    starts, doc_ids, tfs, dl, df = corpus.from_config(config)
    rng = np.random.default_rng([seed, 0])
    ndocs = len(dl)
    status = rng.integers(0, len(corpus.STATUSES), ndocs).astype(np.int32)
    price = rng.integers(0, 1000, ndocs).astype(np.int64)
    vocab = corpus.vocab_strings(len(df))
    seg = corpus.plant_index(client, index, (starts, doc_ids, tfs), vocab,
                             dl, status, price, config["index_settings"])
    build_s = time.time() - t0

    t0 = time.time()
    al = fastpath.get_aligned(seg, "body")
    if al is not None:          # None off the TPU backend (tests only)
        jax.block_until_ready([a for a in (al.d_docs, al.d_tfdl, al.d_imp)
                               if a is not None])
    promote_s = time.time() - t0
    return {"csr": (starts, doc_ids, tfs), "dl": dl, "df": df,
            "vocab": vocab, "build_s": build_s, "promote_s": promote_s,
            "readout": {"postings": int(len(doc_ids)),
                        "aligned_bytes": int(al.nbytes) if al is not None
                        else 0}}


def _weighed(spec: dict) -> dict:
    return dict(spec, weight=len(spec["terms"]))


class _Stream:
    """`queries.QueryStream` as the harness drives it: every spec carries
    its `weight` (the term count), and the twin is `permuted`."""

    def __init__(self, built: dict, traffic: dict, seed: int):
        self._q = queries.QueryStream(built["df"], built["vocab"], seed,
                                      traffic)

    def take(self, n: int) -> list:
        return [_weighed(s) for s in self._q.take(n)]

    def twin(self, spec: dict) -> dict:
        return _weighed(self._q.permuted(spec))

    def reseed(self, seed: int) -> None:
        self._q.reseed(seed)


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    return _Stream(built, traffic, seed)


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    """(spec, response) pairs held to the dense reference by the rule."""
    g = config["guarantees"]
    ref = reference.Reference(built["csr"], built["dl"], k1=g["bm25_k1"],
                              b=g["bm25_b"])
    return reference.hold(held, ref, int(traffic["size"]), int(g["page"]),
                          float(g["score_rtol"]))


def counters(client) -> dict:
    """The serving ladder's counters and the device rescore's."""
    from opensearch_tpu.search import fastpath
    out = {f"fastpath.{k}": v for k, v in dict(fastpath.STATS).items()}
    out.update({f"fastpath.rescore.{k}": v
                for k, v in fastpath.rescore_stats().items()})
    return out
