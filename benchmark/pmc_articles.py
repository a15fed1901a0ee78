"""The article generator and segment wrapper of the deployment kind `pmc`
(OpenSearch Benchmark `pmc`: full-text articles of PubMed Central, one
index, nine mapped fields: the analyzed `body` of some 5,800 tokens, four
short text fields, two keywords, an integer and a date).

No data set is in the image and there is no network, so the articles are
synthetic, from the configuration's `corpus_seed` and `generator`
parameters (docs/BENCH_CORPUS.md, "pmc", has the laws and what they stand
in for). A body is a stream of term ids under a Zipf law over a
vocabulary of pronounceable six-letter words, with **collocations
planted**: with probability `follow` a token is no fresh draw but one of
the `partners` partner terms of the token before it, from a seeded table
(every term has partners, so a partner's own partner can follow it and
three-word chains survive). Independent draws would give a two-word phrase
of mid-frequency words less than one hit a shard, where real text gives
thousands. `generate` draws the flat token stream (`tok`, one int32 a
token, documents end to end under `offsets`) in blocks of consecutive
documents on threads, a block's draws from its own seed, so the stream is
the same whatever the thread count. `invert` turns the stream into
positional postings by one sort a block of packed (term, index) keys,
which leaves each term's positions in (doc, position) order, and lays the
blocks' runs down term by term. `plant_index` wraps everything as one
product `Segment` under an index the client creates through its own API
with the workload's mapping, holding what the refresh path would have
built: positional postings, lengths and (codec v2) impacts for the five
text fields, two keyword columns, two numeric columns. `_source` is made on
demand from the token stream, `body` included."""

from __future__ import annotations

import os
import time

import numpy as np

from big5_events import _keyword
from http_logs_events import zipf_ranks

YEARS = (1990, 2015)            # `timestamp` runs over the source's years
MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
          "Oct", "Nov", "Dec")
TEXT_FIELDS = ("body", "journal", "date", "volume", "issue")
MAPPING = {"properties": {
    "name": {"type": "keyword"},
    "journal": {"type": "text"},
    "date": {"type": "text"},
    "volume": {"type": "text"},
    "issue": {"type": "text"},
    "accession": {"type": "keyword"},
    "timestamp": {"type": "date", "format": "yyyy-MM-dd HH:mm:ss"},
    "pmid": {"type": "integer"},
    "body": {"type": "text"}}}
CONSONANTS, VOWELS = "bcdfghjklmnpqrstvwxyz", "aeiou"
SYLLABLES = 3                   # (21 * 5)^3 = 1,157,625 words
BLOCK_TOKENS = 1 << 23          # tokens a block of consecutive documents


def threads() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def host_gib() -> tuple:
    """(resident now, peak resident so far) of this process, GiB."""
    import resource
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / float(1 << 20)
    try:
        with open("/proc/self/statm") as f:
            now = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") \
                / float(1 << 30)
    except OSError:
        now = peak
    return now, peak


def note(msg: str) -> None:
    """A line on stderr with the process's resident set, now and at its
    peak (the chip machine ends a command at 40 GiB: a run that dies says
    where)."""
    import sys
    now, peak = host_gib()
    print(f"[pmc_articles {time.strftime('%H:%M:%S')}] {msg}; host "
          f"{now:.1f} GiB now, peak {peak:.1f} GiB", file=sys.stderr,
          flush=True)


class Words:
    """Term id -> word: three consonant-vowel syllables, so that the
    standard analyzer leaves a word whole and the ids sort as the words
    do. `words[i]`, `words.of(ids)` (a list)."""

    def __init__(self, n: int):
        syl = sorted(c + v for c in CONSONANTS for v in VOWELS)
        assert n <= len(syl) ** SYLLABLES
        self.n, self._syl = n, syl

    def __len__(self):
        return self.n

    def __getitem__(self, i: int) -> str:
        b, syl = len(self._syl), self._syl
        i = int(i)
        return syl[i // (b * b)] + syl[i // b % b] + syl[i % b]

    def of(self, ids) -> list:
        b = len(self._syl)
        syl = np.asarray(self._syl)
        ids = np.asarray(ids, np.int64)
        parts = np.char.add(np.char.add(syl[ids // (b * b)],
                                        syl[ids // b % b]), syl[ids % b])
        return parts.tolist()


def _lengths(rng, ndocs: int, gen: dict) -> np.ndarray:
    lo, hi = gen["length_clip"]
    raw = rng.lognormal(gen["length_mu"], gen["length_sigma"], ndocs)
    return np.clip(raw, lo, hi).astype(np.int64)


def _partner_table(rng, nterms: int, k: int, s: float) -> np.ndarray:
    """i32[nterms, k]: each term's partners, drawn under the vocabulary's
    own law (so the planted tokens leave the law of the whole as it is),
    no term its own partner and none twice in a row of the table."""
    table = zipf_ranks(rng, nterms * k, nterms, s).reshape(nterms, k)
    own = np.arange(nterms, dtype=np.int32)[:, None]
    while True:
        srt = np.sort(table, axis=1)
        bad = (table == own).any(1) | (srt[:, 1:] == srt[:, :-1]).any(1)
        if not bad.any():
            return table
        table[bad] = zipf_ranks(rng, int(bad.sum()) * k, nterms,
                                s).reshape(-1, k)


def _draw_block(out: np.ndarray, starts: np.ndarray, seed: list,
                nterms: int, s: float, table: np.ndarray, follow: float):
    """The tokens of one block of documents into `out`; `starts` are the
    documents' first tokens (a first token follows nothing)."""
    rng = np.random.default_rng(seed)
    n = len(out)
    out[:] = zipf_ranks(rng, n, nterms, s)
    led = rng.random(n, dtype=np.float32) < follow
    led[starts] = False
    at = np.flatnonzero(led)
    if not len(at):
        return
    which = rng.integers(0, table.shape[1], len(at), dtype=np.int8)
    # a follower of a follower waits for it: depth in its run of followers
    head = np.ones(len(at), bool)
    head[1:] = at[1:] != at[:-1] + 1
    first = np.maximum.accumulate(np.where(head, np.arange(len(at)), 0))
    depth = np.arange(len(at)) - first
    for level in range(int(depth.max()) + 1):
        sel = depth == level
        out[at[sel]] = table[out[at[sel] - 1], which[sel]]


def _blocks(offsets: np.ndarray, block_tokens: int) -> list:
    """[(first doc, one past the last)] of blocks of consecutive documents
    of about `block_tokens` tokens."""
    total = int(offsets[-1])
    cuts = np.searchsorted(offsets, np.arange(block_tokens, total,
                                              block_tokens))
    bounds = np.unique(np.concatenate([[0], cuts, [len(offsets) - 1]]))
    return list(zip(bounds[:-1].tolist(), bounds[1:].tolist()))


def generate(ndocs: int, seed: int, gen: dict) -> dict:
    """The articles of `ndocs` documents: the token stream and the eight
    other fields' columns."""
    from concurrent.futures import ThreadPoolExecutor
    rng = np.random.default_rng([seed, 0])
    nterms, s = int(gen["vocabulary"]), float(gen["zipf_s"])
    lens = _lengths(rng, ndocs, gen)
    offsets = np.zeros(ndocs + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    table = _partner_table(rng, nterms, int(gen["partners"]), s)
    tok = np.empty(int(offsets[-1]), np.int32)
    with ThreadPoolExecutor(threads()) as pool:
        jobs = [pool.submit(_draw_block, tok[offsets[a]: offsets[b]],
                            offsets[a:b] - offsets[a], [seed, 1, i], nterms,
                            s, table, float(gen["follow"]))
                for i, (a, b) in enumerate(_blocks(offsets, BLOCK_TOKENS))]
        for j in jobs:
            j.result()
    note(f"drew {len(tok)} tokens of {ndocs} articles")
    # the other fields: a journal of two words under a Zipf law, a day of
    # the source's years (uniform), volume and issue small numbers
    words = Words(nterms)
    journals = int(gen["journals"])
    jwords = rng.integers(0, nterms, (journals, 2))
    journal = zipf_ranks(rng, ndocs, journals, 1.0)
    day0 = np.datetime64(f"{YEARS[0]}-01-01", "D").astype(np.int64)
    day1 = np.datetime64(f"{YEARS[1] + 1}-01-01", "D").astype(np.int64)
    day = rng.integers(day0, day1, ndocs)
    second = rng.integers(0, 86400, ndocs)
    jnames = np.asarray(words.of(jwords.reshape(-1))).reshape(jwords.shape)
    return {"tok": tok, "offsets": offsets, "lens": lens, "table": table,
            "words": words, "nterms": nterms,
            "journal": journal, "journal_words": jnames,
            "ts_s": day * 86400 + second,
            "volume": rng.integers(1, 60, ndocs),
            "issue": rng.integers(1, 13, ndocs),
            "pmid": 10_000_000 + rng.permutation(4 * ndocs)[:ndocs],
            "live": np.ones(ndocs, bool)}


def collection_frequency(articles: dict) -> np.ndarray:
    """Tokens a term, counted once a corpus."""
    if "cf" not in articles:
        tok, cf = articles["tok"], np.zeros(articles["nterms"], np.int64)
        for lo in range(0, len(tok), BLOCK_TOKENS):     # `bincount` widens
            cf += np.bincount(tok[lo: lo + BLOCK_TOKENS], minlength=len(cf))
        articles["cf"] = cf
    return articles["cf"]


# ---------------------------------------------------------------------
# inversion: the token stream -> positional postings
# ---------------------------------------------------------------------

def _run_dest(base: np.ndarray, counts: np.ndarray,
              terms: np.ndarray) -> np.ndarray:
    """Where the sorted `terms` of one block go: `base[t]` is term t's first
    free slot, and the block's run of t lies in order behind it."""
    ahead = base - (np.cumsum(counts, dtype=np.int64) - counts)
    dest = np.arange(len(terms), dtype=np.int64)
    dest += ahead[terms]
    return dest


def _invert_block(tok, offsets, a: int, b: int, base, counts, positions):
    """One block of documents [a, b): its positions written where they
    belong, its postings returned as (term, doc, tf) in (term, doc) order."""
    lo, hi = int(offsets[a]), int(offsets[b])
    n = hi - lo
    key = tok[lo:hi].astype(np.int64)
    key <<= 32
    key |= np.arange(n, dtype=np.int64)
    key.sort()                  # unique keys: (term, doc, position) order
    terms = (key >> 32).astype(np.int32)
    key &= 0xFFFFFFFF
    doc = np.repeat(np.arange(a, b, dtype=np.int32),
                    np.diff(offsets[a: b + 1]))[key]
    key -= offsets[doc] - lo
    positions[_run_dest(base, counts, terms)] = key
    first = np.ones(n, bool)
    first[1:] = (terms[1:] != terms[:-1]) | (doc[1:] != doc[:-1])
    at = np.flatnonzero(first)
    return terms[at], doc[at], np.diff(at, append=n).astype(np.int32)


def invert(tok: np.ndarray, offsets: np.ndarray, nterms: int, pool) -> dict:
    """Positional postings of the stream: `held` the terms that occur
    (ascending), `starts` i64 over them, `doc_ids` i32, `tfs` f32,
    `pos_starts` i64 and `positions` i32 as `PostingsBlock` holds them."""
    blocks = _blocks(offsets, BLOCK_TOKENS)
    counts = list(pool.map(
        lambda ab: np.bincount(tok[offsets[ab[0]]: offsets[ab[1]]],
                               minlength=nterms), blocks))
    base = np.zeros(nterms, np.int64)
    np.cumsum(np.sum(counts, axis=0)[:-1], out=base[1:])
    positions = np.empty(len(tok), np.int32)
    jobs = []
    for (a, b), c in zip(blocks, counts):
        jobs.append(pool.submit(_invert_block, tok, offsets, a, b,
                                base.copy(), c, positions))
        base += c
    parts = [j.result() for j in jobs]
    del counts
    # a block's postings come in term order, so its counts a term say
    # which term each is: the term column (4 bytes a posting) goes here
    pcounts = [np.bincount(t, minlength=nterms) for t, _d, _f in parts]
    parts = [(d, f) for _t, d, f in parts]
    df = np.sum(pcounts, axis=0)
    base = np.zeros(nterms, np.int64)
    np.cumsum(df[:-1], out=base[1:])
    doc_ids = np.empty(int(df.sum()), np.int32)
    tfs = np.empty(len(doc_ids), np.float32)

    def lay(part, at, c):
        dest = np.arange(len(part[0]), dtype=np.int64)
        dest += np.repeat(at - (np.cumsum(c, dtype=np.int64) - c), c)
        doc_ids[dest] = part[0]
        tfs[dest] = part[1]
    jobs = []
    for part, c in zip(parts, pcounts):
        jobs.append(pool.submit(lay, part, base.copy(), c))
        base += c
    for j in jobs:
        j.result()
    held = np.flatnonzero(df)
    starts = np.zeros(len(held) + 1, np.int64)
    np.cumsum(df[held], out=starts[1:])
    pos_starts = np.zeros(len(doc_ids) + 1, np.int64)
    np.cumsum(tfs, dtype=np.int64, out=pos_starts[1:])
    return {"held": held, "starts": starts, "doc_ids": doc_ids, "tfs": tfs,
            "pos_starts": pos_starts, "positions": positions}


def _slot_postings(slots: np.ndarray, nterms: int) -> dict:
    """Positional postings of a short field of one token a slot:
    `slots[s, d]` is the term at position s of document d."""
    from concurrent.futures import ThreadPoolExecutor
    nslots, ndocs = slots.shape
    tok = np.ascontiguousarray(slots.T).reshape(-1).astype(np.int32)
    offsets = np.arange(ndocs + 1, dtype=np.int64) * nslots
    with ThreadPoolExecutor(1) as pool:
        return invert(tok, offsets, nterms, pool)


# ---------------------------------------------------------------------
# _source and the segment
# ---------------------------------------------------------------------

def field_values(articles: dict, i: int) -> dict:
    """Document i's eight short fields as the corpus spells them."""
    a = articles
    t = time.gmtime(int(a["ts_s"][i]))
    journal = " ".join(w.capitalize()
                       for w in a["journal_words"][a["journal"][i]])
    date = f"{t.tm_year} {MONTHS[t.tm_mon - 1]} {t.tm_mday}"
    volume, issue = int(a["volume"][i]), int(a["issue"][i])
    row = int(a.get("first", 0)) + i
    return {"name": f"{journal.replace(' ', '_')}_{date.replace(' ', '_')}"
                    f"_{volume}({issue})_{row}",
            "journal": journal, "date": date, "volume": str(volume),
            "issue": str(issue), "accession": f"PMC{2_000_000 + row}",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S", t),
            "pmid": int(a["pmid"][i])}


def body(articles: dict, i: int) -> str:
    lo, hi = articles["offsets"][i], articles["offsets"][i + 1]
    return " ".join(articles["words"].of(articles["tok"][lo:hi]))


class _LazySources:
    """An article's `_source`, made on demand from the columns and the
    token stream (`body` included: 38 KB a document)."""

    def __init__(self, articles: dict):
        self.a = articles

    def __len__(self):
        return len(self.a["lens"])

    def __getitem__(self, i):
        return dict(field_values(self.a, int(i)), body=body(self.a, int(i)))


def _text_block(field: str, inv: dict, vocab: list):
    from opensearch_tpu.index.segment import PostingsBlock
    return PostingsBlock(field=field, vocab=vocab,
                         terms={t: i for i, t in enumerate(vocab)},
                         starts=inv["starts"], doc_ids=inv["doc_ids"],
                         tfs=inv["tfs"], pos_starts=inv["pos_starts"],
                         positions=inv["positions"])


def _short_text(articles: dict) -> dict:
    """field -> (PostingsBlock, tokens a document) of the four short text
    fields, as the standard analyzer would have made them (lower case)."""
    a, ndocs = articles, len(articles["lens"])
    day = (a["ts_s"] // 86400).astype("datetime64[D]")
    month = day.astype("datetime64[M]")
    out = {}
    for field, slots in (
            ("journal", list(a["journal_words"][a["journal"]].T)),
            ("date", [(day.astype("datetime64[Y]").astype(np.int64)
                       + 1970).astype(str),
                      np.asarray([m.lower() for m in MONTHS])[
                          month.astype(np.int64) % 12],
                      ((day - month).astype(np.int64) + 1).astype(str)]),
            ("volume", [a["volume"].astype(str)]),
            ("issue", [a["issue"].astype(str)])):
        vocab, codes = np.unique(np.concatenate(slots), return_inverse=True)
        inv = _slot_postings(codes.reshape(len(slots), ndocs), len(vocab))
        out[field] = (_text_block(field, inv, vocab.tolist()),
                      np.full(ndocs, len(slots), np.int64))
    return out


class _Ids:
    """Doc-id strings of rows [first, first + n), made on demand."""

    def __init__(self, first: int, n: int):
        self.first, self.n = first, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [str(self.first + j) for j in range(*i.indices(self.n))]
        return str(self.first + i)


def part(articles: dict, a: int, b: int) -> dict:
    """Articles [a, b) as a collection of their own (the table, the words
    and the vocabulary stay the whole's)."""
    lo, hi = articles["offsets"][a], articles["offsets"][b]
    out = dict(articles, tok=articles["tok"][lo:hi],
               offsets=articles["offsets"][a: b + 1] - lo, first=a)
    out.pop("cf", None)
    for k in ("lens", "journal", "ts_s", "volume", "issue", "pmid", "live"):
        out[k] = articles[k][a:b]
    return out


def make_segment(articles: dict, name: str = "pmc_0"):
    """One product `Segment` holding the nine fields of `articles`, its
    document ids counted from `articles["first"]` (0 for a whole
    collection)."""
    from concurrent.futures import ThreadPoolExecutor

    from opensearch_tpu.index.segment import (CODEC_V2, NumericColumn,
                                              Segment, TextFieldStats,
                                              default_codec_version)
    a, ndocs = articles, len(articles["lens"])
    present = np.ones(ndocs, bool)
    shared = {"docs": np.arange(ndocs, dtype=np.int32),
              "ones": np.ones(ndocs, np.float32),
              "row_starts": np.arange(ndocs + 1, dtype=np.int64)}
    with ThreadPoolExecutor(threads()) as pool:
        short = pool.submit(_short_text, a)
        values = pool.submit(lambda: [field_values(a, i)
                                      for i in range(ndocs)])
        inv = invert(a["tok"], a["offsets"], a["nterms"], pool)
        note(f"{name}: inverted into {len(inv['doc_ids'])} postings")
        vocab = a["words"].of(inv["held"])
        text = {"body": (_text_block("body", inv, vocab), a["lens"])}
        text.update(short.result())
        kw = {f: _keyword(f, shared["docs"], [v[f] for v in values.result()],
                          ndocs, shared) for f in ("name", "accession")}

    def numeric(field, vals):
        return NumericColumn(field=field, kind="int",
                             values=np.asarray(vals, np.int64),
                             present=present)
    postings = {f: pb for f, (pb, _dl) in text.items()}
    postings.update({f: pb for f, (pb, _col) in kw.items()})
    seg = Segment(
        name=name, ndocs=ndocs, postings=postings,
        numeric_cols={"timestamp": numeric("timestamp", a["ts_s"] * 1000),
                      "pmid": numeric("pmid", a["pmid"])},
        keyword_cols={f: col for f, (_pb, col) in kw.items()}, geo_cols={},
        doc_lens={f: dl for f, (_pb, dl) in text.items()},
        text_stats={f: TextFieldStats(doc_count=ndocs, sum_dl=int(dl.sum()))
                    for f, (_pb, dl) in text.items()},
        ids=[], sources=[])
    seg.ids = _Ids(int(a.get("first", 0)), ndocs)
    seg.sources = _LazySources(a)
    seg.id2doc = {}
    seg.live = np.asarray(a["live"], dtype=bool).copy()
    if default_codec_version() >= CODEC_V2:
        seg.build_impacts()     # as the refresh path builds them
    note(f"{name}: segment of {ndocs} articles wrapped")
    return seg


def plant_index(client, index: str, articles: dict, settings: dict,
                cuts=()):
    """Create `index` through the client with the workload's mapping and
    plant the articles as one segment (as one more a document in `cuts`,
    each segment the articles up to the next cut). -> the first Segment."""
    client.indices.create(index, {"settings": settings, "mappings": MAPPING})
    svc = client.node.indices[index]
    bounds = [0, *cuts, len(articles["lens"])]
    segs = [make_segment(part(articles, a, b), f"pmc_{i}")
            for i, (a, b) in enumerate(zip(bounds, bounds[1:]))]
    svc.shards[0].segments = segs
    svc.generation += 1
    return segs[0]
