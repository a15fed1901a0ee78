"""The question generator and segment wrapper of the deployment kind
`nested` (OpenSearch Benchmark `nested`: a StackOverflow dump, one index,
a question a document: `qid`, `title`, `tag`, `user`, `creationDate` and
`answers`, a nested object an answer with `date` and `user`).

No data set is in the image and there is no network, so the questions are
synthetic, from the configuration's `corpus_seed` and `generator`
parameters (docs/BENCH_CORPUS.md, "nested", has the laws and what they
stand in for). `generate` makes columns in bulk with numpy, never a JSON
document: a question's tags, title words and answers are rows
`off[i]:off[i + 1]` of flat arrays. `plant_index` wraps them as ONE parent
`Segment` whose `nested["answers"]` is a `NestedBlock` over a child
`Segment` of the answers, children in parent order, under an index the
client creates through its own API with the workload's mapping: what the
refresh path builds for those documents (`benchmark/tests/test_nested.py`
and `tests/test_nested_deployment.py` hold a small one equal to a refreshed
one, array for array). `qid` and the users are distinct values by the
million, so their vocabularies, the document ids and the `_source`s are
made on demand and never held as Python strings."""

from __future__ import annotations

import numpy as np

from big5_events import _words, iso_ms
from corpus import _LazyIds
from http_logs_events import zipf_ranks

SPAN_START_MS = 1217548800000      # 2008-08-01T00:00:00Z: the site opens
SPAN_END_MS = 1410652800000        # 2014-09-14T00:00:00Z: the dump is cut
DOC_BITS = 32                      # a row of either space (2^25 answers)
PATH = "answers"
DATE, USER = PATH + ".date", PATH + ".user"
MAX_TAGS = 5

MAPPING = {"properties": {
    "qid": {"type": "keyword"}, "title": {"type": "text"},
    "tag": {"type": "keyword"}, "user": {"type": "keyword"},
    "creationDate": {"type": "date"},
    PATH: {"type": "nested", "properties": {
        "date": {"type": "date"}, "user": {"type": "keyword"}}}}}


def mandelbrot_ranks(rng, n: int, size: int, s: float, q: float):
    """`n` ranks in [0, size) under a Zipf-Mandelbrot law
    P(r) ~ (r + 1 + q)^-s, by the inverse of the continuous law's
    distribution (one power a draw; s != 1)."""
    lo, hi = (1.0 + q) ** (1.0 - s), (size + 1.0 + q) ** (1.0 - s)
    u = rng.random(n)
    u *= hi - lo
    u += lo
    np.power(u, 1.0 / (1.0 - s), out=u)
    u -= q
    r = u.astype(np.int32)
    r -= 1
    return np.clip(r, 0, size - 1, out=r)


def user_name(code: int) -> str:
    """Zero-padded, so that names sort as their codes do."""
    return f"u{int(code):07d}"


def _tags(rng, n: int, gen: dict):
    """A question's tags: 1-5 (the configuration's `tags_pmf`), drawn from
    the tag law with no tag twice in one question. -> (off i64[n + 1],
    codes i32[total], ascending inside a question)."""
    pmf = np.asarray(gen["tags_pmf"], np.float64)
    assert len(pmf) == MAX_TAGS and abs(pmf.sum() - 1.0) < 1e-9
    ntags, s, q = int(gen["tags"]), float(gen["tag_s"]), float(gen["tag_q"])
    count = rng.choice(MAX_TAGS, n, p=pmf).astype(np.int8) + 1
    drawn = mandelbrot_ranks(rng, n * MAX_TAGS, ntags, s, q) \
        .reshape(n, MAX_TAGS)
    used = np.arange(MAX_TAGS, dtype=np.int8)[None, :] < count[:, None]
    big = np.int32(ntags)           # the slots a question does not use
    while True:
        drawn[~used] = big
        drawn.sort(axis=1)
        twice = np.zeros(drawn.shape, bool)
        twice[:, 1:] = (drawn[:, 1:] == drawn[:, :-1]) & (drawn[:, 1:] < big)
        if not twice.any():
            break
        drawn[twice] = mandelbrot_ranks(rng, int(twice.sum()), ntags, s, q)
        used = drawn < big          # sorted: the used slots lead
    off = np.zeros(n + 1, np.int64)
    np.cumsum(count, out=off[1:])
    return off, drawn[drawn < big]


def _answer_counts(rng, n: int, gen: dict) -> np.ndarray:
    """Answers a question: none with probability `answers_none`, else the
    whole part of a Pareto draw (x_m 1, shape `answers_alpha`: P(k or
    more) = k^-alpha), clipped at `answers_max`."""
    u = rng.random(n)
    np.power(1.0 - u, -1.0 / float(gen["answers_alpha"]), out=u)
    k = np.minimum(u, float(gen["answers_max"])).astype(np.int32)
    k[rng.random(n) < float(gen["answers_none"])] = 0
    return k


def generate(ndocs: int, seed: int, gen: dict) -> dict:
    """The columns of `ndocs` questions, in creation order: `created_ms`
    i64 (non-decreasing), `asker` i32 (a user's code), `tag_off` /
    `tags` (codes into `tag_names`), `title_off` / `title_tok` (indices
    into `dictionary`), `ans_off` i64[ndocs + 1] with `ans_date_ms` i64 and
    `ans_user` i32 an answer row (an answer's date is its question's plus
    a log-uniform delay, so the answers' dates are in no row order).
    Groups of columns have random streams of their own (spawned from
    `seed`) and are drawn side by side on threads (numpy releases the
    lock)."""
    from concurrent.futures import ThreadPoolExecutor
    (r_ts, r_tag, r_title, r_ans, r_delay, r_user, r_auser) = \
        np.random.default_rng([int(seed), 51]).spawn(7)
    nusers = min(int(gen["users"]), max(ndocs // 4, 8))
    ndict = int(gen["dictionary_words"])
    lo_w, hi_w = (int(x) for x in gen["title_words"])

    def created():
        ts = r_ts.integers(SPAN_START_MS, SPAN_END_MS, ndocs, dtype=np.int64)
        ts.sort()
        return ts

    def title():
        n_words = r_title.integers(lo_w, hi_w + 1, ndocs, dtype=np.int32)
        off = np.zeros(ndocs + 1, np.int64)
        np.cumsum(n_words, out=off[1:])
        return off, zipf_ranks(r_title, int(off[-1]), ndict,
                               float(gen["word_zipf"]))

    def answers():
        k = _answer_counts(r_ans, ndocs, gen)
        off = np.zeros(ndocs + 1, np.int64)
        np.cumsum(k, out=off[1:])
        return off

    with ThreadPoolExecutor(5) as pool:
        f_created, f_tags = pool.submit(created), \
            pool.submit(_tags, r_tag, ndocs, gen)
        f_title, f_ans = pool.submit(title), pool.submit(answers)
        f_asker = pool.submit(zipf_ranks, r_user, ndocs, nusers,
                              float(gen["user_zipf"]))
        ans_off = f_ans.result()
        nans = int(ans_off[-1])
        f_auser = pool.submit(zipf_ranks, r_auser, nans, nusers,
                              float(gen["user_zipf"]))
        # the delay: log-uniform over `delay_s` (a minute to years: as
        # likely between one and ten minutes as between one and ten
        # months), to the millisecond
        lo_d, hi_d = (np.log(float(x) * 1e3) for x in gen["delay_s"])
        delay = r_delay.random(nans)
        delay *= hi_d - lo_d
        delay += lo_d
        np.exp(delay, out=delay)
        created_ms = f_created.result()
        ans_date = np.repeat(created_ms, np.diff(ans_off))
        ans_date += delay.astype(np.int64)
        tag_off, tags = f_tags.result()
        title_off, title_tok = f_title.result()
        return {"created_ms": created_ms, "asker": f_asker.result(),
                "tag_off": tag_off, "tags": tags,
                "tag_names": _words(int(gen["tags"]), 2, 23),
                "title_off": title_off, "title_tok": title_tok,
                "dictionary": _words(ndict, 3, 911),
                "ans_off": ans_off, "ans_date_ms": ans_date,
                "ans_user": f_auser.result(), "users": nusers}


def tag_question_counts(q: dict) -> np.ndarray:
    """Questions a tag (a question holds a tag once)."""
    return np.bincount(q["tags"], minlength=len(q["tag_names"]))


# ---------------------------------------------------------------------
# what is made on demand: ids, vocabularies of a value a row, _source
# ---------------------------------------------------------------------

class _Lazy:
    """A read-only sequence of `n` items made by `_at(i)`."""

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._at(j) for j in range(*i.indices(self.n))]
        i = int(i)
        if i < 0:
            i += self.n
        if not 0 <= i < self.n:
            raise IndexError(i)
        return self._at(i)


class _Terms:
    """term -> row of a vocabulary made on demand: what `PostingsBlock`
    asks of its `terms` (`get`, `in`, `[]`, `len`)."""

    def __init__(self, vocab, row_of):
        self.vocab, self.row_of = vocab, row_of

    def __len__(self):
        return len(self.vocab)

    def get(self, term, default=None):
        row = self.row_of(term)
        return default if row is None else row

    def __contains__(self, term):
        return self.row_of(term) is not None

    def __getitem__(self, term):
        row = self.row_of(term)
        if row is None:
            raise KeyError(term)
        return row


class _RowNumbers(_Lazy):
    """The strings "0" .. str(n - 1) in sorted (lexicographic) order:
    `order[o]` is the row whose number is the o-th string, `rank` its
    inverse. Two int32 planes, not n strings and a dict of them."""

    def __init__(self, n: int):
        self.n = n
        rows = np.arange(n, dtype=np.int64)
        digits = np.ones(n, np.int64)
        for d in range(1, len(str(max(n - 1, 1)))):
            digits += rows >= 10 ** d
        width = int(digits.max()) if n else 1
        # left-aligned digits, then the shorter string first
        key = rows * 10 ** (width - digits) * 16 + digits
        self.order = np.argsort(key, kind="stable").astype(np.int32)
        self.rank = np.empty(n, np.int32)
        self.rank[self.order] = np.arange(n, dtype=np.int32)

    def _at(self, o):
        return str(int(self.order[o]))

    def row_of(self, term):
        if not (isinstance(term, str) and term.isdigit()
                and str(int(term)) == term and int(term) < self.n):
            return None
        return int(self.rank[int(term)])


class _UserNames(_Lazy):
    """`user_name` of the codes that occur, ascending (zero-padded names
    sort as their codes)."""

    def __init__(self, codes: np.ndarray):
        self.codes, self.n = codes, len(codes)

    def _at(self, o):
        return user_name(self.codes[o])

    def row_of(self, term):
        if not (isinstance(term, str) and len(term) == 8 and term[0] == "u"
                and term[1:].isdigit()):
            return None
        code = self.codes.dtype.type(int(term[1:]))     # no cast of `codes`
        at = int(np.searchsorted(self.codes, code))
        return at if at < self.n and self.codes[at] == code else None


class _AnswerIds(_Lazy):
    """A child row's id, as the refresh path names it."""

    def __init__(self, ans_off: np.ndarray):
        self.off, self.n = ans_off, int(ans_off[-1])

    def _at(self, i):
        pid = int(np.searchsorted(self.off, i, side="right")) - 1
        return f"{pid}#{PATH}#{i - int(self.off[pid])}"


def answer_source(q: dict, row: int) -> dict:
    return {"date": iso_ms(int(q["ans_date_ms"][row])),
            "user": user_name(q["ans_user"][row])}


def question_source(q: dict, i: int) -> dict:
    """A question's `_source`, its `answers` array with it."""
    t0, t1 = int(q["title_off"][i]), int(q["title_off"][i + 1])
    g0, g1 = int(q["tag_off"][i]), int(q["tag_off"][i + 1])
    a0, a1 = int(q["ans_off"][i]), int(q["ans_off"][i + 1])
    return {"qid": str(i),
            "title": " ".join(q["dictionary"][w] for w in q["title_tok"][t0:t1]),
            "tag": [q["tag_names"][c] for c in q["tags"][g0:g1]],
            "user": user_name(q["asker"][i]),
            "creationDate": iso_ms(int(q["created_ms"][i])),
            PATH: [answer_source(q, r) for r in range(a0, a1)]}


class _Sources(_Lazy):
    def __init__(self, q: dict, n: int, make):
        self.q, self.n, self.make = q, n, make

    def _at(self, i):
        return self.make(self.q, i)


# ---------------------------------------------------------------------
# the segment
# ---------------------------------------------------------------------

def _sorted_rows(names, seen: np.ndarray, ncodes: int):
    """The codes `seen` as rows of a vocabulary sorted by name: -> (vocab,
    row i32[ncodes], -1 where a code does not occur)."""
    held = [names[c] for c in seen.tolist()]
    order = sorted(range(len(seen)), key=held.__getitem__)
    row = np.full(ncodes, -1, np.int32)
    row[seen[order]] = np.arange(len(seen), dtype=np.int32)
    return [held[i] for i in order], row


def _by_term(rows: np.ndarray, docs: np.ndarray, nrows: int):
    """(row, doc) pairs, a pair once, as postings by row, documents
    ascending inside a row: -> (starts i64[nrows + 1], doc_ids i32, tfs
    f32: how often the pair came). One sort of packed keys."""
    keys = rows.astype(np.int64)
    keys <<= DOC_BITS
    keys |= docs
    keys.sort()
    first = np.ones(len(keys), bool)
    np.not_equal(keys[1:], keys[:-1], out=first[1:])
    at = np.flatnonzero(first)
    tfs = np.diff(at, append=len(keys)).astype(np.float32)
    keys = keys[at]
    starts = np.zeros(nrows + 1, np.int64)
    np.cumsum(np.bincount(keys >> DOC_BITS, minlength=nrows), out=starts[1:])
    keys &= (1 << DOC_BITS) - 1
    return starts, keys.astype(np.int32), tfs


def _single_keyword(field: str, rows: np.ndarray, vocab, terms, docs):
    """(postings, column) of a keyword with one value a document, `rows`
    its row in the sorted `vocab`, `docs` the row numbers (an arange)."""
    from opensearch_tpu.index.segment import KeywordColumn, PostingsBlock
    n = len(rows)
    starts, doc_ids, tfs = _by_term(rows, docs[:n], len(vocab))
    block = PostingsBlock(field=field, vocab=vocab, terms=terms,
                          starts=starts, doc_ids=doc_ids, tfs=tfs)
    return block, KeywordColumn(
        field=field, vocab=vocab,
        starts=np.arange(n + 1, dtype=np.int64), ords=rows,
        doc_of_value=docs[:n].astype(np.int32), min_ord=rows)


def _user_keyword(field: str, codes: np.ndarray, docs):
    seen = np.unique(codes)
    vocab = _UserNames(seen)
    rows = np.searchsorted(seen, codes).astype(np.int32)
    return _single_keyword(field, rows, vocab, _Terms(vocab, vocab.row_of),
                           docs)


def _tag_keyword(q: dict, ndocs: int):
    """`tag`: several values a document. The column by value (a question's
    ordinals ascending, `doc_of_value` beside them) and the postings by
    term."""
    from opensearch_tpu.index.segment import KeywordColumn, PostingsBlock
    counts = tag_question_counts(q)
    vocab, row = _sorted_rows(q["tag_names"], np.flatnonzero(counts),
                              len(counts))
    doc_of_value = np.repeat(np.arange(ndocs, dtype=np.int32),
                             np.diff(q["tag_off"]))
    # a question's ordinals ascending: one sort of (doc, ordinal) keys
    keys = doc_of_value.astype(np.int64)
    keys <<= 32
    keys |= row[q["tags"]]
    keys.sort()
    ords = (keys & 0xFFFFFFFF).astype(np.int32)
    starts_doc = q["tag_off"].astype(np.int64)
    min_ord = np.full(ndocs, -1, np.int32)
    has = np.diff(starts_doc) > 0
    min_ord[has] = ords[starts_doc[:-1][has]]
    starts, doc_ids, tfs = _by_term(ords, doc_of_value, len(vocab))
    block = PostingsBlock(field="tag", vocab=vocab,
                          terms={v: i for i, v in enumerate(vocab)},
                          starts=starts, doc_ids=doc_ids, tfs=tfs)
    return block, KeywordColumn(field="tag", vocab=vocab, starts=starts_doc,
                                ords=ords, doc_of_value=doc_of_value,
                                min_ord=min_ord)


def _title_postings(q: dict, ndocs: int):
    """`PostingsBlock` of the analyzed `title` (lower-case words, which the
    standard analyzer leaves whole), only the words that occur as rows, and
    the documents' lengths. No positions: no traffic here asks a phrase."""
    from opensearch_tpu.index.segment import PostingsBlock
    tok = q["title_tok"]
    seen = np.flatnonzero(np.bincount(tok, minlength=len(q["dictionary"])))
    vocab, row = _sorted_rows(q["dictionary"], seen, len(q["dictionary"]))
    lens = np.diff(q["title_off"])
    docs = np.repeat(np.arange(ndocs, dtype=np.int64), lens)
    starts, doc_ids, tfs = _by_term(row[tok], docs, len(vocab))
    block = PostingsBlock(field="title", vocab=vocab,
                          terms={t: i for i, t in enumerate(vocab)},
                          starts=starts, doc_ids=doc_ids, tfs=tfs)
    return block, lens.astype(np.int64)


def build_segments(q: dict, name: str = "nested_0"):
    """The parent `Segment` of the questions `q`, its `NestedBlock` of the
    answers with it, as a refresh leaves them."""
    from concurrent.futures import ThreadPoolExecutor

    from opensearch_tpu.index.segment import (CODEC_V2, NestedBlock,
                                              NumericColumn, Segment,
                                              TextFieldStats,
                                              default_codec_version)
    ndocs, nans = len(q["created_ms"]), int(q["ans_off"][-1])
    assert max(ndocs, nans) < 1 << 31
    docs = np.arange(max(ndocs, nans), dtype=np.int64)
    with ThreadPoolExecutor(6) as pool:
        f_title = pool.submit(_title_postings, q, ndocs)
        f_tag = pool.submit(_tag_keyword, q, ndocs)
        f_user = pool.submit(_user_keyword, "user", q["asker"], docs)
        f_auser = pool.submit(_user_keyword, USER, q["ans_user"], docs)
        qids = _RowNumbers(ndocs)
        f_qid = pool.submit(_single_keyword, "qid", qids.rank, qids,
                            _Terms(qids, qids.row_of), docs)
        kw = {"qid": f_qid.result(), "tag": f_tag.result(),
              "user": f_user.result()}
        title_pb, dl = f_title.result()
        auser_pb, auser_col = f_auser.result()
    child = Segment(
        name=f"{name}/{PATH}", ndocs=nans, postings={USER: auser_pb},
        numeric_cols={DATE: NumericColumn(
            field=DATE, kind="int", values=q["ans_date_ms"],
            present=np.ones(nans, bool))},
        keyword_cols={USER: auser_col}, geo_cols={}, doc_lens={},
        text_stats={}, ids=[], sources=[])
    child.ids = _AnswerIds(q["ans_off"])
    child.sources = _Sources(q, nans, answer_source)
    parent_of = np.repeat(np.arange(ndocs, dtype=np.int32),
                          np.diff(q["ans_off"]))
    postings = {f: pb for f, (pb, _col) in kw.items()}
    postings["title"] = title_pb
    seg = Segment(
        name=name, ndocs=ndocs, postings=postings,
        numeric_cols={"creationDate": NumericColumn(
            field="creationDate", kind="int", values=q["created_ms"],
            present=np.ones(ndocs, bool))},
        keyword_cols={f: col for f, (_pb, col) in kw.items()}, geo_cols={},
        doc_lens={"title": dl},
        text_stats={"title": TextFieldStats(doc_count=ndocs,
                                            sum_dl=int(dl.sum()))},
        ids=[], sources=[], nested={PATH: NestedBlock(child, parent_of)})
    seg.ids = _LazyIds(ndocs)
    seg.sources = _Sources(q, ndocs, question_source)
    if default_codec_version() >= CODEC_V2:
        seg.build_impacts()     # as the refresh path builds them
    return seg


def plant_index(client, index: str, q: dict, settings: dict):
    """Create `index` through the client with the workload's mapping and
    plant one segment holding the questions `q`. -> the Segment."""
    client.indices.create(index, {"settings": settings, "mappings": MAPPING})
    svc = client.node.indices[index]
    seg = build_segments(q)
    svc.shards[0].segments = [seg]
    svc.generation += 1
    return seg
