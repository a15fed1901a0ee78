"""Passage embeddings for the deployment kind `vectorsearch`: a seeded
stand-in for Cohere's `wikipedia-22-12-en-embeddings` (768 floats a passage,
compared by inner product, NOT unit length), which is not in the image and
cannot be fetched. numpy only; imports nothing of the program.

What an approximate index sees in a real embedding set, and what is built
here (`docs/BENCH_CORPUS.md`, "vectorsearch"):

- **Topics of uneven size.** `topics` latent topics; a passage's topic is
  drawn with weight 1 / (rank + `zipf_offset`) ** `zipf_s`: of the cell's
  16,384 the largest holds 24,539 of 2,000,000 passages (a dozen balanced
  lists' worth), the median topic 34, and 13,660 topics hold under 100.
  Balanced lists therefore spill, and most queries find part of their
  100 neighbours in other topics.
- **Centres of unequal length, in families.** A topic's centre is
  `length * unit(subject_share * subject + sqrt(1 - subject_share^2) *
  own)` + a mean vector all passages share (`mean_length`): `subjects`
  broad families give neighbouring topics, `length` is lognormal
  (`length_sigma`), so the inner product is not the cosine and the long
  centres draw probes.
- **Spread that is not isotropic.** Within a topic a passage is its centre
  + `z B` + isotropic noise: `B` is one basis of `spread_rank` directions
  whose variances fall as 1 / j ** `spread_decay` (total `spread`^2),
  `noise` the isotropic part's length, `row_length_sigma` a lognormal
  factor a row (0 in the cell). Isotropic noise alone in 768 dimensions
  makes every pair of passages equally far apart: no index finds
  neighbours there, and none are there to find.

Queries are further draws of the same mixture (`draw` under another stream
key): held-out passages, never rows of the corpus.

Measured at the cell's size (2,000,000 x 768, `corpus_seed` 20221201, the
configuration's `generator`; the program's IVF at its defaults: nlist 1,414,
nprobe 176, cap 2,122) on the chip (my chip run, PR 36, call 1; 64 held-out
queries): 425,613 rows (21.3%) spilled from their nearest list; the exact
scan's median gap between the 100th and the 101st score is 1.7e-4 of the
score; recall@100 of the IVF route is 0.966 at nprobe 44 (nlist / 32),
0.9916 at 176 (nlist / 8, the default) and 0.9986 at 707 (nlist / 2): the
operating point lies on the curve's rising part. `docs/BENCH_CORPUS.md`
("vectorsearch") has the laws and their reasons."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from corpus import _LazyIds

BLOCK = 32768           # rows a generator stream makes: fixes the draws
CORPUS_STREAM, QUERY_STREAM, TWIN_STREAM = 1, 2, 7


def _unit(a: np.ndarray) -> np.ndarray:
    return a / np.linalg.norm(a, axis=-1, keepdims=True)


def mixture(seed: int, p: dict) -> dict:
    """The latent structure every draw shares: topic weights (cumulative),
    centres f32[topics, dims], the spread's basis f32[rank, dims] with its
    standard deviations folded in, the isotropic noise's per-coordinate
    standard deviation."""
    rng = np.random.default_rng([int(seed), 0])
    dims, topics = int(p["dims"]), int(p["topics"])
    w = 1.0 / (np.arange(1, topics + 1) + float(p["zipf_offset"])) \
        ** float(p["zipf_s"])
    subject = _unit(rng.standard_normal((int(p["subjects"]), dims)))
    of = rng.integers(0, len(subject), topics)
    share = float(p["subject_share"])
    direction = _unit(share * subject[of] + np.sqrt(1.0 - share * share)
                      * _unit(rng.standard_normal((topics, dims))))
    length = np.exp(float(p["length_sigma"]) * rng.standard_normal(topics))
    mean = float(p["mean_length"]) * _unit(rng.standard_normal(dims))
    # the largest topics are not the longest: lengths are dealt at random
    centres = (direction * length[:, None] + mean).astype(np.float32)
    rank = int(p["spread_rank"])
    basis, _ = np.linalg.qr(rng.standard_normal((dims, rank)))
    var = 1.0 / np.arange(1, rank + 1) ** float(p["spread_decay"])
    sd = float(p["spread"]) * np.sqrt(var / var.sum())
    return {"dims": dims, "cum": np.cumsum(w / w.sum()),
            "centres": centres,
            "basis": (basis.T * sd[:, None]).astype(np.float32),
            "noise_sd": float(p["noise"]) / np.sqrt(dims),
            "row_length_sigma": float(p["row_length_sigma"])}


def _block(mix: dict, key: list, n: int, out: np.ndarray,
           topic: np.ndarray) -> None:
    rng = np.random.default_rng(key)
    t = np.minimum(np.searchsorted(mix["cum"], rng.random(n)),
                   len(mix["cum"]) - 1)
    z = rng.standard_normal((n, len(mix["basis"])), dtype=np.float32)
    np.matmul(z, mix["basis"], out=out)
    out += mix["centres"][t]
    out += mix["noise_sd"] * rng.standard_normal(out.shape, dtype=np.float32)
    out *= np.exp(mix["row_length_sigma"] * rng.standard_normal(
        n, dtype=np.float32))[:, None]
    topic[:] = t


def draw(mix: dict, n: int, seed: int, stream: int,
         threads: int = 8) -> tuple:
    """`n` draws of the mixture under (`seed`, `stream`): -> (f32[n, dims],
    i32[n] topics). Made in blocks of `BLOCK` rows, each from its own
    generator, so the rows do not depend on how many threads made them."""
    out = np.empty((n, mix["dims"]), np.float32)
    topic = np.empty(n, np.int32)
    spans = [(i, lo, min(lo + BLOCK, n))
             for i, lo in enumerate(range(0, n, BLOCK))]

    def one(span):
        i, lo, hi = span
        _block(mix, [int(seed), int(stream), i], hi - lo, out[lo:hi],
               topic[lo:hi])
    if len(spans) > 1 and threads > 1:
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(one, spans))
    else:
        for span in spans:
            one(span)
    return out, topic


def generate(ndocs: int, corpus_seed: int, params: dict) -> dict:
    """The collection: `vectors` f32[ndocs, dims], `topic` i32[ndocs] (for
    read-outs; no request sees it) and the `mixture` queries are drawn
    from."""
    mix = mixture(corpus_seed, params)
    vectors, topic = draw(mix, int(ndocs), corpus_seed, CORPUS_STREAM)
    return {"vectors": vectors, "topic": topic, "mixture": mix,
            "corpus_seed": int(corpus_seed)}


def query_vector(mix: dict, corpus_seed: int, seed: int, stream: int,
                 serial: int) -> np.ndarray:
    """One held-out draw, f32[dims]: the `serial`-th of (`seed`, `stream`)."""
    out = np.empty((1, mix["dims"]), np.float32)
    _block(mix, [int(corpus_seed), int(stream), int(seed), int(serial)], 1,
           out, np.empty(1, np.int32))
    return out[0]


MAPPING_FIELD = "target_field"


def mapping(config: dict) -> dict:
    """The workload's index body, `method` as the configuration gives it."""
    return {"properties": {MAPPING_FIELD: {
        "type": "knn_vector", "dimension": int(config["dimension"]),
        "method": dict(config["method"],
                       space_type=config["space_type"])}}}


class _LazySources:
    """No request of this deployment reads `_source` (`stored_fields`
    `_none_`); a document's is its vector as a list, made on demand."""

    def __init__(self, vectors: np.ndarray):
        self.v = vectors

    def __len__(self):
        return len(self.v)

    def __getitem__(self, i):
        return {MAPPING_FIELD: self.v[i].tolist()}


def plant_index(client, index: str, corpus: dict, config: dict):
    """Create `index` through the client with the workload's mapping and
    plant one segment holding the one vector column. -> the Segment."""
    from opensearch_tpu.index.segment import Segment, VectorColumn
    client.indices.create(index, {"settings": config["index_settings"],
                                  "mappings": mapping(config)})
    svc = client.node.indices[index]
    ft = svc.mappings.resolve_field(MAPPING_FIELD)
    vectors = corpus["vectors"]
    ndocs = len(vectors)
    col = VectorColumn(MAPPING_FIELD, vectors, np.ones(ndocs, bool),
                       ft.vector_similarity, method=ft.vector_method)
    seg = Segment(name="vectors0", ndocs=ndocs, postings={}, numeric_cols={},
                  keyword_cols={}, geo_cols={}, doc_lens={}, text_stats={},
                  ids=[], sources=[], vector_cols={MAPPING_FIELD: col})
    seg.ids = _LazyIds(ndocs)
    seg.sources = _LazySources(vectors)
    seg.id2doc = {}
    seg.live = np.ones(ndocs, dtype=bool)
    svc.shards[0].segments = [seg]
    svc.generation += 1
    return seg
