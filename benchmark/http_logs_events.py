"""The event generator and segment wrapper of the deployment kind
`http_logs` (OpenSearch Benchmark `http_logs`: web-server log events
with `@timestamp`, `clientip`, `request`, `status`, `size`).

No data set is in the image and there is no network, so the events are
synthetic, from the configuration's `corpus_seed` and `generator`
parameters, in arrival order (docs/BENCH_CORPUS.md, "http_logs", has the
laws and what they stand in for). `generate` makes the columns in bulk
with numpy; `plant_index` wraps them as one product `Segment` under an
index the client creates through its own API with the workload's
mapping, holding what the refresh path would have built for those five
fields: numeric doc-value columns, the `clientip` and `request.raw` term
postings, the `request.raw` keyword column, and the analyzed `request`
text postings with document lengths and (codec v2) impacts. Positions are
not built (no traffic here asks a phrase)."""

from __future__ import annotations

import time

import numpy as np

from corpus import _LazyIds

SPAN_START_S = 893894400        # 1998-04-30T00:00:00Z
SPAN_DAYS = 88                  # to 1998-07-27T00:00:00Z, exclusive
SPAN_S = SPAN_DAYS * 86400
FIRST_MATCH_DAY, LAST_MATCH_DAY = 41, 73    # 1998-06-10 .. 1998-07-12
DOC_BITS = 26                   # a shard's doc id fits (67,108,864 rows)

MAPPING = {"properties": {
    "@timestamp": {"type": "date"},
    "clientip": {"type": "ip"},
    "request": {"type": "text",
                "fields": {"raw": {"type": "keyword", "ignore_above": 256}}},
    "status": {"type": "integer"},
    "size": {"type": "integer"}}}

SECTIONS = ("images", "news", "teams", "venues", "history", "tickets",
            "competition", "individuals", "member", "playing", "enfetes",
            "frntpage", "help", "hosts", "legal", "nav", "results", "shop",
            "stats", "tv")
LANGS = ("english", "french")
EXTS = ("gif", "gif", "gif", "gif", "gif", "gif", "html", "html", "html",
        "jpg", "htm", "class")


def minute_weights() -> np.ndarray:
    """Relative arrival rate of every minute of the span: a ramp up to the
    tournament, a daily cycle that peaks at 15:00 UTC, and two-hour bursts
    at 3.5 times the rate on match days (19:00 UTC every match day, 15:30
    too on two days of three)."""
    minute = np.arange(SPAN_S // 60, dtype=np.int64)
    day, tod = minute // 1440, (minute % 1440) / 60.0
    growth = np.interp(day, [0, FIRST_MATCH_DAY, LAST_MATCH_DAY,
                             LAST_MATCH_DAY + 3, SPAN_DAYS - 1],
                       [0.35, 1.0, 1.3, 0.5, 0.3])
    daily = 1.0 + 0.55 * np.cos(2 * np.pi * (tod - 15.0) / 24.0)
    match_day = (day >= FIRST_MATCH_DAY) & (day <= LAST_MATCH_DAY)
    burst = match_day & (((tod >= 19.0) & (tod < 21.0))
                         | ((day % 3 != 0) & (tod >= 15.5) & (tod < 17.5)))
    return growth * daily * np.where(burst, 3.5, 1.0)


def zipf_ranks(rng, n: int, size: int, s: float) -> np.ndarray:
    """`n` ranks in [0, size) under a Zipf-like law P(r) ~ (r + 1)^-s, by
    the inverse of the continuous law's distribution (one `exp` a draw)."""
    u = rng.random(n)
    if abs(s - 1.0) < 1e-9:
        u *= np.log(size + 1.0)
        np.exp(u, out=u)
    else:
        u *= (size + 1.0) ** (1.0 - s) - 1.0
        u += 1.0
        np.power(u, 1.0 / (1.0 - s), out=u)
    r = u.astype(np.int32)
    r -= 1
    return np.minimum(r, size - 1, out=r)


def request_lines(rng, n: int) -> list:
    """`n` distinct request lines in the 1998 World Cup site's shape."""
    lines = []
    for i in range(n):
        method = "GET" if rng.random() < 0.985 else ("HEAD", "POST")[i % 2]
        lang = LANGS[int(rng.random() < 0.3)] + "/" if rng.random() < 0.6 \
            else ""
        section = SECTIONS[int(rng.integers(len(SECTIONS)))]
        ext = EXTS[int(rng.integers(len(EXTS)))]
        proto = "1.0" if rng.random() < 0.85 else "1.1"
        lines.append(f"{method} /{lang}{section}/p{i:05d}.{ext} "
                     f"HTTP/{proto}")
    return lines


def _timestamps(rng, ndocs: int) -> np.ndarray:
    w = minute_weights()
    counts = rng.multinomial(ndocs, w / w.sum())
    ts = np.repeat(np.arange(len(w), dtype=np.int64) * 60, counts)
    ts += (rng.random(ndocs, dtype=np.float32) * 60).astype(np.int64)
    ts.sort()                       # a uniform second of the minute
    ts += SPAN_START_S
    ts *= 1000
    return ts


def _status_and_size(rng, ndocs: int, gen: dict):
    codes = np.asarray([int(c) for c in gen["status_shares"]], np.int64)
    cum = np.cumsum([float(p) for p in gen["status_shares"].values()])
    u = rng.random(ndocs)
    u *= cum[-1]
    status = codes[np.minimum(np.searchsorted(cum, u), len(codes) - 1)]
    size = rng.lognormal(float(gen["size_mu"]), float(gen["size_sigma"]),
                         ndocs).astype(np.int64)
    np.minimum(size, int(gen["size_max"]), out=size)
    size[status == 304] = 0
    return status, size


def generate(ndocs: int, seed: int, gen: dict) -> dict:
    """The columns of `ndocs` events, in arrival order: `ts_ms` i64 (whole
    seconds, non-decreasing), `status` i64, `size` i64, `client` i32 (an
    index into `client_ips` u32) and `req` i32 (an index into `lines`).
    Every column has a random stream of its own (spawned from `seed`), so
    they are drawn side by side on threads (numpy releases the lock)."""
    from concurrent.futures import ThreadPoolExecutor
    r_ts, r_status, r_client, r_req, r_rest = np.random.default_rng(
        [int(seed), 28]).spawn(5)
    nclients = min(int(gen["clients"]), max(ndocs // 4, 16))
    nlines = min(int(gen["request_lines"]), max(ndocs // 16, 16))
    with ThreadPoolExecutor(4) as pool:
        ts_ms = pool.submit(_timestamps, r_ts, ndocs)
        status_size = pool.submit(_status_and_size, r_status, ndocs, gen)
        client = pool.submit(zipf_ranks, r_client, ndocs, nclients,
                             float(gen["client_zipf"]))
        req = pool.submit(zipf_ranks, r_req, ndocs, nlines,
                          float(gen["request_zipf"]))
        # a client's address: a seeded stride through public-looking IPv4
        # space, one each (the stride is coprime to the modulus 223 * 2^24,
        # so no two clients share one)
        client_ips = ((np.arange(nclients, dtype=np.uint64) * 2654435761
                       + int(r_rest.integers(1 << 32))) % (223 << 24)
                      + (1 << 24)).astype(np.uint32)
        lines = request_lines(r_rest, nlines)
        status, size = status_size.result()
        return {"ts_ms": ts_ms.result(), "status": status, "size": size,
                "client": client.result(), "client_ips": client_ips,
                "req": req.result(), "lines": lines}


def iso_seconds(epoch_s: int) -> str:
    """`1998-05-01T00:00:17Z` of a whole epoch second."""
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(epoch_s))


def ip_string(ip: int) -> str:
    return f"{ip >> 24}.{(ip >> 16) & 255}.{(ip >> 8) & 255}.{ip & 255}"


class _LazySources:
    """An event's `_source`, made on demand from the columns."""

    def __init__(self, events: dict):
        self.e = events

    def __len__(self):
        return len(self.e["ts_ms"])

    def __getitem__(self, i):
        e = self.e
        return {"@timestamp": iso_seconds(int(e["ts_ms"][i]) // 1000),
                "clientip": ip_string(int(e["client_ips"][e["client"][i]])),
                "request": e["lines"][int(e["req"][i])],
                "status": int(e["status"][i]), "size": int(e["size"][i])}


def _grouped(rows: np.ndarray, nrows: int):
    """Documents grouped by `rows` (one row a document), ascending inside a
    row: -> (starts i64[nrows + 1], doc_ids i32). One sort of packed
    (row, doc) keys, which numpy runs far faster than a stable argsort."""
    keys = rows.astype(np.int64)
    keys <<= DOC_BITS
    keys |= np.arange(len(rows), dtype=np.int64)
    keys.sort()
    keys &= (1 << DOC_BITS) - 1
    starts = np.zeros(nrows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=nrows), out=starts[1:])
    return starts, keys.astype(np.int32)


def _term_postings(field: str, values: list, rows: np.ndarray):
    """`PostingsBlock` of a field with one term a document: `values[k]` is
    the term of the documents whose `rows` entry is k (`values` sorted)."""
    from opensearch_tpu.index.segment import PostingsBlock
    starts, doc_ids = _grouped(rows, len(values))
    return PostingsBlock(field=field, vocab=values,
                         terms={v: i for i, v in enumerate(values)},
                         starts=starts, doc_ids=doc_ids,
                         tfs=np.ones(len(doc_ids), np.float32))


def _text_postings(field: str, line_terms: list, req: np.ndarray,
                   by_line, pool):
    """`PostingsBlock` of the analyzed text field: `line_terms[l]` is the
    token list of line l and `req[d]` the line of document d, so a term's
    documents are those of the lines that hold it. `by_line` is
    `_grouped(req)`: a line's documents, ascending. Every term's row is
    filled in place from its lines' slices and sorted where there are
    several; the rows are independent, so `pool` takes them side by side.
    -> (block, document lengths i64)."""
    from opensearch_tpu.index.segment import PostingsBlock
    nlines = len(line_terms)
    lstarts, ldocs = by_line
    tf_of = {}                          # term -> {line: tf}
    for l, toks in enumerate(line_terms):
        for t in toks:
            d = tf_of.setdefault(t, {})
            d[l] = d.get(l, 0) + 1
    vocab = sorted(tf_of)
    line_df = np.diff(lstarts)
    lines_of = [np.fromiter(tf_of[t], np.int64, len(tf_of[t]))
                for t in vocab]
    starts = np.zeros(len(vocab) + 1, np.int64)
    np.cumsum([int(line_df[ls].sum()) for ls in lines_of], out=starts[1:])
    doc_ids = np.empty(int(starts[-1]), np.int32)
    tfs = np.ones(int(starts[-1]), np.float32)

    def fill(r: int) -> None:
        out, at = doc_ids[starts[r]: starts[r + 1]], 0
        for l in lines_of[r]:
            docs = ldocs[lstarts[l]: lstarts[l + 1]]
            out[at: at + len(docs)] = docs
            at += len(docs)
        if len(lines_of[r]) > 1:
            out.sort()
        tf = tf_of[vocab[r]]
        if any(v != 1 for v in tf.values()):
            tf_line = np.zeros(nlines, np.float32)
            tf_line[lines_of[r]] = [tf[int(l)] for l in lines_of[r]]
            tfs[starts[r]: starts[r + 1]] = tf_line[req[out]]
    # the long rows first, so that no thread is left with one at the end
    list(pool.map(fill, np.argsort(-np.diff(starts)).tolist()))
    block = PostingsBlock(field=field, vocab=vocab,
                          terms={t: i for i, t in enumerate(vocab)},
                          starts=starts, doc_ids=doc_ids, tfs=tfs)
    line_len = np.asarray([len(t) for t in line_terms], np.int64)
    return block, line_len[req]


def plant_index(client, index: str, events: dict, settings: dict):
    """Create `index` through the client with the workload's mapping and
    plant one segment holding the five fields of `events`. -> the Segment."""
    from concurrent.futures import ThreadPoolExecutor

    from opensearch_tpu.index.segment import (CODEC_V2, KeywordColumn,
                                              NumericColumn, Segment,
                                              TextFieldStats,
                                              default_codec_version)
    client.indices.create(index, {"settings": settings, "mappings": MAPPING})
    svc = client.node.indices[index]
    mappings = svc.mappings
    ndocs = len(events["ts_ms"])
    present = np.ones(ndocs, bool)

    def numeric(field, values):
        return NumericColumn(field=field, kind="int",
                             values=np.asarray(values, np.int64),
                             present=present)

    def clientip():
        # ip: the term (the address as a string) and the numeric doc value
        # (IPv4-mapped, as `mappings._ip_to_int` gives it)
        ips = events["client_ips"]
        seen = np.unique(events["client"])
        strings = [ip_string(int(ips[c])) for c in seen]
        order = sorted(range(len(seen)), key=strings.__getitem__)
        row_of_client = np.full(len(ips), -1, np.int32)
        row_of_client[seen[order]] = np.arange(len(seen), dtype=np.int32)
        block = _term_postings("clientip", [strings[i] for i in order],
                               row_of_client[events["client"]])
        value = (ips.astype(np.int64) | (0xFFFF << 32))[events["client"]]
        return block, numeric("clientip", value)

    with ThreadPoolExecutor(8) as pool:
        ip_parts = pool.submit(clientip)
        # request.raw: the line as one keyword; request: its analyzed tokens
        lines, req = events["lines"], events["req"]
        lorder = sorted(np.unique(req).tolist(), key=lines.__getitem__)
        row_of_line = np.full(len(lines), -1, np.int32)
        row_of_line[lorder] = np.arange(len(lorder), dtype=np.int32)
        raw_rows = row_of_line[req]
        raw_vocab = [lines[l] for l in lorder]
        raw_pb = _term_postings("request.raw", raw_vocab, raw_rows)
        raw_kw = KeywordColumn(field="request.raw", vocab=raw_vocab,
                               starts=np.arange(ndocs + 1, dtype=np.int64),
                               ords=raw_rows,
                               doc_of_value=np.arange(ndocs, dtype=np.int32),
                               min_ord=raw_rows)
        analyzer = mappings.index_analyzer(mappings.resolve_field("request"))
        req_pb, dl = _text_postings(
            "request", [analyzer.terms(v) for v in raw_vocab], raw_rows,
            (raw_pb.starts, raw_pb.doc_ids), pool)
        ip_pb, ip_col = ip_parts.result()

    seg = Segment(
        name="httplogs0", ndocs=ndocs,
        postings={"clientip": ip_pb, "request": req_pb,
                  "request.raw": raw_pb},
        numeric_cols={"@timestamp": numeric("@timestamp", events["ts_ms"]),
                      "clientip": ip_col,
                      "status": numeric("status", events["status"]),
                      "size": numeric("size", events["size"])},
        keyword_cols={"request.raw": raw_kw}, geo_cols={},
        doc_lens={"request": dl},
        text_stats={"request": TextFieldStats(doc_count=ndocs,
                                              sum_dl=int(dl.sum()))},
        ids=[], sources=[])
    seg.ids = _LazyIds(ndocs)
    seg.sources = _LazySources(events)
    seg.id2doc = {}
    seg.live = np.ones(ndocs, dtype=bool)
    if default_codec_version() >= CODEC_V2:
        seg.build_impacts()     # as the refresh path builds them
    svc.shards[0].segments = [seg]
    svc.generation += 1
    return seg
