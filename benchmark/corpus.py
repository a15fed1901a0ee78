"""The benchmark's own corpus generator and segment wrapper.

Copies of `bench.build_corpus` and `bench.make_index` as they stood when PR
24 defined the benchmark (PERF.md lists the originals under Open questions):
later PRs may change `bench.py`, and may not change the yardstick. The
generator's parameters are a configuration's `generator` object and its
`corpus_seed`. The wrapper plants the CSR arrays as a product
`Segment` under an index the client creates through its normal API, the way
the refresh path would have built it (codec v2 impacts included)."""

from __future__ import annotations

import numpy as np

STATUSES = ["archived", "draft", "published"]


def build_corpus(ndocs: int, vocab: int, avg_dl: float, seed: int,
                 zipf_a: float = 1.15, dl_sigma: float = 0.4,
                 dl_clip=(8, 256)):
    """Zipf(`zipf_a`) term ids over a `vocab`, lognormal document lengths
    around `avg_dl` clipped to `dl_clip` -> CSR postings by term:
    (starts i64[vocab+1], doc_ids i32, tfs f32, dl i64[ndocs], df i64)."""
    rng = np.random.default_rng(seed)
    dl = np.clip(rng.lognormal(np.log(avg_dl), dl_sigma, ndocs),
                 dl_clip[0], dl_clip[1]).astype(np.int64)
    total = int(dl.sum())
    doc_of_tok = np.repeat(np.arange(ndocs, dtype=np.int64), dl)
    terms = rng.zipf(zipf_a, total).astype(np.int64)
    terms = np.where(terms > vocab, rng.integers(1, vocab, total), terms) - 1
    keys = terms * ndocs + doc_of_tok
    del terms, doc_of_tok
    uniq, counts = np.unique(keys, return_counts=True)
    del keys
    term_arr = uniq // ndocs
    doc_ids = (uniq % ndocs).astype(np.int32)
    tfs = counts.astype(np.float32)
    df = np.bincount(term_arr, minlength=vocab)
    starts = np.zeros(vocab + 1, dtype=np.int64)
    np.cumsum(df, out=starts[1:])
    return starts, doc_ids, tfs, dl, df


def from_config(config: dict):
    """`build_corpus` with a configuration file's `ndocs`, `corpus_seed` and
    `generator` parameters."""
    gen = config["generator"]
    return build_corpus(
        int(config["ndocs"]), int(gen["vocab"]), float(gen["avg_dl"]),
        int(config["corpus_seed"]), zipf_a=float(gen["zipf_a"]),
        dl_sigma=float(gen["dl_sigma"]), dl_clip=tuple(gen["dl_clip"]))


def vocab_strings(n: int) -> list:
    """`t0000017`-style words: the standard analyzer keeps them whole."""
    return [f"t{i:07d}" for i in range(n)]


class _LazyIds:
    """Doc-id strings made on demand (a fetch touches ~10 a query)."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [str(j) for j in range(*i.indices(self.n))]
        return str(i)


class _LazySources:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return {"doc": int(i)}


def plant_index(client, index: str, csr, vocab: list, dl, status, price,
                settings: dict):
    """Create `index` through the client and plant one segment holding the
    CSR `body` postings plus the `status` keyword and `price` integer
    columns. -> the Segment."""
    from opensearch_tpu.index.segment import (CODEC_V2, KeywordColumn,
                                              NumericColumn, PostingsBlock,
                                              Segment, TextFieldStats,
                                              default_codec_version)
    starts, doc_ids, tfs = csr
    ndocs = len(dl)
    pb = PostingsBlock(field="body", vocab=vocab,
                       terms={t: i for i, t in enumerate(vocab)},
                       starts=starts, doc_ids=doc_ids, tfs=tfs)
    status = status.astype(np.int32)
    kw = KeywordColumn(field="status", vocab=STATUSES,
                       starts=np.arange(ndocs + 1, dtype=np.int64),
                       ords=status,
                       doc_of_value=np.arange(ndocs, dtype=np.int32),
                       min_ord=status)
    # keyword term queries run against postings, like the real segment
    # builder: one CSR row per status value
    sorder = np.argsort(status, kind="stable").astype(np.int32)
    sstarts = np.zeros(len(STATUSES) + 1, np.int64)
    np.cumsum(np.bincount(status, minlength=len(STATUSES)), out=sstarts[1:])
    spb = PostingsBlock(field="status", vocab=STATUSES,
                        terms={v: i for i, v in enumerate(STATUSES)},
                        starts=sstarts, doc_ids=sorder,
                        tfs=np.ones(ndocs, np.float32))
    nc = NumericColumn(field="price", kind="int",
                       values=price.astype(np.int64),
                       present=np.ones(ndocs, bool))
    seg = Segment(
        name="bench0", ndocs=ndocs, postings={"body": pb, "status": spb},
        numeric_cols={"price": nc}, keyword_cols={"status": kw},
        geo_cols={}, doc_lens={"body": dl},
        text_stats={"body": TextFieldStats(doc_count=ndocs,
                                           sum_dl=int(dl.sum()))},
        ids=[], sources=[])
    seg.ids = _LazyIds(ndocs)
    seg.sources = _LazySources(ndocs)
    seg.id2doc = {}
    seg.live = np.ones(ndocs, dtype=bool)
    if default_codec_version() >= CODEC_V2:
        seg.build_impacts()     # as the refresh path builds them
    client.indices.create(index, {
        "settings": settings,
        "mappings": {"properties": {
            "body": {"type": "text"}, "status": {"type": "keyword"},
            "price": {"type": "integer"}}}})
    svc = client.node.indices[index]
    svc.shards[0].segments = [seg]
    svc.generation += 1
    return seg
