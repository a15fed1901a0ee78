"""The event generator and segment wrapper of the deployment kind `big5`
(OpenSearch Benchmark `big5`: ECS-shaped log events, one index, 26 mapped
fields: `@timestamp` and two more dates, the analyzed `message`, twenty
keywords, two longs).

No data set is in the image and there is no network, so the events are
synthetic, from the configuration's `corpus_seed` and `generator`
parameters, in arrival order (docs/BENCH_CORPUS.md, "big5", has the laws
and what they stand in for). A fleet of agents (one host, one region, one
name, two UUIDs each) ships log streams (each one agent's, in one log
group, named by two words as Elastic's corpus generator names them); an
event picks its stream under a Zipf law and with it its agent, host, region
and file path, and draws its process, its seven message words, its event id
and its metrics on its own. `generate` makes the columns in bulk with
numpy and hands every keyword as small-integer codes into a list of
values (`columns["kw"][field]`), never a string a document;
`plant_index` wraps them as one product `Segment` under an index the client
creates through its own API with the workload's mapping, holding what the
refresh path would have built for those 26 fields: five numeric doc-value
columns, twenty keyword columns with their term postings, and the analyzed
`message` postings with document lengths and (codec v2) impacts. Positions
are not built (no traffic here asks a phrase)."""

from __future__ import annotations

import time

import numpy as np

from corpus import _LazyIds
from http_logs_events import _grouped, zipf_ranks

SPAN_START_S = 1672531200       # 2023-01-01T00:00:00Z
SPAN_DAYS = 14                  # to 2023-01-15T00:00:00Z, exclusive
SPAN_S = SPAN_DAYS * 86400
DOC_BITS = 25                   # a chip's share of the shard fits (2^25 rows)
MESSAGE_TOKENS = 18             # month day HH MM SS ip a b c d process + 7

DATES = ("@timestamp", "event.ingested", "aws.cloudwatch.ingestion_time")
LONGS = ("metrics.size", "metrics.tmin")
KEYWORDS = ("process.name", "cloud.region", "aws.cloudwatch.log_group",
            "aws.cloudwatch.log_stream", "log.file.path", "agent.id",
            "agent.name", "agent.ephemeral_id", "agent.type", "agent.version",
            "event.id", "event.dataset", "input.type", "meta.file", "tags",
            "data_stream.dataset", "data_stream.namespace",
            "data_stream.type", "ecs.version", "host.name")
CONSTANTS = {"agent.type": "filebeat", "event.dataset": "generic",
             "input.type": "aws-cloudwatch",
             "tags": "preserve_original_event",
             "data_stream.dataset": "generic",
             "data_stream.namespace": "default", "data_stream.type": "logs",
             "ecs.version": "8.0.0"}


def _nested(flat: dict) -> dict:
    """{"a.b.c": spec} -> the object mapping the workload's index.json
    spells ({"a": {"properties": {"b": ...}}})."""
    out: dict = {}
    for path, spec in flat.items():
        at = out
        *objects, leaf = path.split(".")
        for name in objects:
            at = at.setdefault(name, {"properties": {}})["properties"]
        at[leaf] = spec
    return out


MAPPING = {"properties": _nested(dict(
    [(f, {"type": "date"}) for f in DATES]
    + [("message", {"type": "text"})]
    + [(f, {"type": "keyword"}) for f in KEYWORDS]
    + [(f, {"type": "long"}) for f in LONGS]))}
NFIELDS = len(DATES) + 1 + len(KEYWORDS) + len(LONGS)
assert NFIELDS == 26

# what the fleet is made of (the sizes are the configuration's `generator`)
REGIONS = ("us-east-1", "us-west-2", "eu-west-1", "eu-central-1",
           "ap-southeast-1", "ap-northeast-1", "us-east-2", "ap-south-1",
           "ap-southeast-2", "eu-west-2", "sa-east-1", "ca-central-1",
           "us-west-1", "ap-northeast-2", "eu-north-1", "eu-west-3",
           "ap-east-1", "me-south-1", "af-south-1", "eu-south-1",
           "ap-northeast-3", "ap-southeast-3", "me-central-1", "eu-south-2",
           "eu-central-2", "ap-south-2")
PROCESSES = ("journal", "kernel", "systemd", "sshd", "cron", "dockerd",
             "kubelet", "containerd", "chronyd", "sudo", "rsyslogd",
             "dhclient")
LOG_GROUPS = ("/var/log/messages", "/var/log/syslog", "/var/log/secure",
              "/var/log/cron", "/var/log/audit", "/var/log/kern",
              "/var/log/daemon", "/var/log/cloud-init")
VERSIONS = ("8.8.0", "8.7.1", "8.6.2", "7.17.10")
_CONS, _VOWS = "bcdfghjklmnprstvw", "aeiou"


def _words(n: int, syllables: int, salt: int) -> list:
    """`n` distinct pronounceable words of `syllables` consonant-vowel
    pairs and a closing consonant, the same for every seed (a word list is
    part of the naming scheme, not of a collection)."""
    space = (len(_CONS) * len(_VOWS)) ** syllables * len(_CONS)
    assert n <= space
    # a stride coprime to the space walks it without a repeat
    stride = 2654435761 % space
    while np.gcd(stride, space) != 1:
        stride += 1
    out = []
    for i in range(n):
        code, w = (salt + i * stride) % space, []
        code, c = divmod(code, len(_CONS))
        for _ in range(syllables):
            code, v = divmod(code, len(_VOWS))
            code, k = divmod(code, len(_CONS))
            w.append(_CONS[k] + _VOWS[v])
        out.append("".join(w) + _CONS[c])
    return out


def name_words(n: int):
    """The two word lists a two-word name ("luckcrafter") is drawn from:
    `n` first words of five letters, `n` second words of seven."""
    return _words(n, 2, 17), _words(n, 3, 4099)


def second_weights(daily_swing: float) -> np.ndarray:
    """Relative arrival rate of every minute of the span: a daily cycle
    around 1 that peaks at 15:00 UTC."""
    tod = (np.arange(SPAN_S // 60, dtype=np.int64) % 1440) / 60.0
    return 1.0 + daily_swing * np.cos(2 * np.pi * (tod - 15.0) / 24.0)


def _timestamps(rng, ndocs: int, daily_swing: float) -> np.ndarray:
    w = second_weights(daily_swing)
    counts = rng.multinomial(ndocs, w / w.sum())
    ts = np.repeat(np.arange(len(w), dtype=np.int64) * 60, counts)
    ts += (rng.random(ndocs, dtype=np.float32) * 60).astype(np.int64)
    ts.sort()                       # a uniform second of the minute
    ts += SPAN_START_S
    return ts


def _uuids(rng, n: int) -> list:
    raw = rng.integers(0, 1 << 32, (n, 4), dtype=np.uint64)
    return [f"{a:08x}-{b >> 16:04x}-{b & 0xFFFF:04x}-{c >> 16:04x}-"
            f"{c & 0xFFFF:04x}{d:08x}" for a, b, c, d in raw.tolist()]


def generate(ndocs: int, seed: int, gen: dict) -> dict:
    """The columns of `ndocs` events, in arrival order: `ts_s` i64 (whole
    epoch seconds, non-decreasing), `ingested_ms` / `ingestion_ms` i64,
    `size` / `tmin` i32, `words` u16[ndocs, 7] (indices into `dictionary`),
    `host_octets` u8[agents, 4] with `agent` i32 (an event's agent), and
    `kw`: field -> (codes, values), a keyword's value of event d being
    `values[codes[d]]` (values in no order, not all of them need occur).
    Groups of columns have random streams of their own (spawned from
    `seed`) and are drawn side by side on threads (numpy releases the
    lock)."""
    from concurrent.futures import ThreadPoolExecutor
    (r_ts, r_stream, r_proc, r_event, r_words, r_metrics, r_lag,
     r_fleet) = np.random.default_rng([int(seed), 43]).spawn(8)
    nagents = min(int(gen["agents"]), max(ndocs // 200, 8))
    nstreams = nagents * int(gen["streams_per_agent"])
    nwords = int(gen["name_words"])
    ndict = min(int(gen["dictionary_words"]), 1 << 16)
    with ThreadPoolExecutor(6) as pool:
        ts_s = pool.submit(_timestamps, r_ts, ndocs,
                           float(gen["daily_swing"]))
        stream = pool.submit(zipf_ranks, r_stream, ndocs, nstreams,
                             float(gen["stream_zipf"]))
        process = pool.submit(zipf_ranks, r_proc, ndocs, len(PROCESSES),
                              float(gen["process_zipf"]))
        event = pool.submit(lambda: r_event.integers(
            0, nwords * nwords, ndocs, dtype=np.int32))
        words = pool.submit(lambda: zipf_ranks(
            r_words, ndocs * 7, ndict, float(gen["word_zipf"])
        ).astype(np.uint16).reshape(ndocs, 7))
        metrics = pool.submit(lambda: (
            r_metrics.integers(1, int(gen["metrics_size_max"]) + 1, ndocs,
                               dtype=np.int32),
            r_metrics.integers(1, int(gen["metrics_tmin_max"]) + 1, ndocs,
                               dtype=np.int32)))
        # the fleet: every agent's region, version, ids and address, every
        # stream's agent, log group and two-word name
        firsts, seconds = name_words(nwords)

        def two_word(codes):
            return [firsts[c // nwords] + seconds[c % nwords]
                    for c in codes.tolist()]
        agent_region = zipf_ranks(r_fleet, nagents, len(REGIONS),
                                  float(gen["region_zipf"]))
        agent_version = zipf_ranks(r_fleet, nagents, len(VERSIONS), 1.0)
        agent_names = two_word(r_fleet.choice(nwords * nwords, nagents,
                                              replace=False))
        stream_names = two_word(r_fleet.choice(nwords * nwords, nstreams,
                                               replace=False))
        stream_agent = r_fleet.integers(0, nagents, nstreams, dtype=np.int32)
        stream_group = zipf_ranks(r_fleet, nstreams, len(LOG_GROUPS),
                                  float(gen["group_zipf"]))
        # a private address each: 10.x.y.z, a seeded stride with no repeat
        addr = (np.arange(nagents, dtype=np.int64) * 2654435761
                + int(r_fleet.integers(1 << 24))) % (1 << 24)
        host_octets = np.stack([np.full(nagents, 10), addr >> 16,
                                (addr >> 8) & 255, addr & 255],
                               axis=1).astype(np.uint8)
        agent_ids, ephemeral_ids = _uuids(r_fleet, nagents), \
            _uuids(r_fleet, nagents)
        ts_s, stream = ts_s.result(), stream.result()
        lo, hi = (int(x) * 1000 for x in gen["ingest_lag_s"])
        ts_ms = ts_s * 1000
        ingestion_ms = ts_ms + r_lag.integers(0, 5000, ndocs)
        ingested_ms = ts_ms + r_lag.integers(lo, hi, ndocs)
        size, tmin = metrics.result()
        agent = stream_agent[stream]
        hour = ((ts_s - SPAN_START_S) // 3600).astype(np.int16)
        hours = np.arange(SPAN_DAYS * 24) * 3600 + SPAN_START_S
        zero = np.zeros(ndocs, np.int8)
        kw = {f: (zero, [v]) for f, v in CONSTANTS.items()}
        kw.update({
            "process.name": (process.result().astype(np.int8),
                             list(PROCESSES)),
            "cloud.region": (agent_region[agent].astype(np.int8),
                             list(REGIONS)),
            "aws.cloudwatch.log_group": (
                stream_group[stream].astype(np.int8), list(LOG_GROUPS)),
            "aws.cloudwatch.log_stream": (stream, stream_names),
            "log.file.path": (stream, [
                f"{LOG_GROUPS[g]}/{s}"
                for g, s in zip(stream_group.tolist(), stream_names)]),
            "agent.id": (agent, agent_ids),
            "agent.name": (agent, agent_names),
            "agent.ephemeral_id": (agent, ephemeral_ids),
            "agent.version": (agent_version[agent].astype(np.int8),
                              list(VERSIONS)),
            "event.id": (event.result(), _TwoWords(firsts, seconds)),
            "meta.file": (hour, [
                time.strftime("%Y-%m-%d/", time.gmtime(int(h)))
                + f"{int(h)}-gotext.ndjson.gz" for h in hours]),
            "host.name": (agent, ["ip-" + "-".join(map(str, o))
                                  for o in host_octets.tolist()])})
        assert set(kw) == set(KEYWORDS)
        return {"ts_s": ts_s, "ingested_ms": ingested_ms,
                "ingestion_ms": ingestion_ms, "size": size, "tmin": tmin,
                "agent": agent, "host_octets": host_octets,
                "words": words.result(), "dictionary": _words(ndict, 3, 911),
                "kw": kw}


class _TwoWords:
    """The two-word names of the whole space, made on demand: code
    `a * n + b` is `firsts[a] + seconds[b]`."""

    def __init__(self, firsts: list, seconds: list):
        self.firsts, self.seconds = firsts, seconds

    def __len__(self):
        return len(self.firsts) * len(self.seconds)

    def __getitem__(self, code):
        a, b = divmod(int(code), len(self.seconds))
        return self.firsts[a] + self.seconds[b]


def iso_seconds(epoch_s: int) -> str:
    """`2023-01-02T03:04:05Z` of a whole epoch second."""
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(epoch_s))


def iso_ms(epoch_ms: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(epoch_ms // 1000)) \
        + f".{epoch_ms % 1000:03d}Z"


def message(events: dict, i: int) -> str:
    """`Jan  2 03:04:05 ip-10-0-3-200 systemd: <seven words>`."""
    t = time.gmtime(int(events["ts_s"][i]))
    codes, names = events["kw"]["process.name"]
    host = "-".join(map(str, events["host_octets"][events["agent"][i]]))
    words = " ".join(events["dictionary"][w] for w in events["words"][i])
    return (f"{time.strftime('%b', t)} {t.tm_mday:>2} "
            f"{time.strftime('%H:%M:%S', t)} ip-{host} "
            f"{names[codes[i]]}: {words}")


class _LazySources:
    """An event's `_source`, made on demand from the columns."""

    def __init__(self, events: dict):
        self.e = events

    def __len__(self):
        return len(self.e["ts_s"])

    def __getitem__(self, i):
        e = self.e
        flat = {"@timestamp": iso_seconds(int(e["ts_s"][i])),
                "event.ingested": iso_ms(int(e["ingested_ms"][i])),
                "aws.cloudwatch.ingestion_time":
                    iso_ms(int(e["ingestion_ms"][i])),
                "message": message(e, i),
                "metrics.size": int(e["size"][i]),
                "metrics.tmin": int(e["tmin"][i])}
        for f, (codes, values) in e["kw"].items():
            flat[f] = values[int(codes[i])]
        flat["tags"] = [flat["tags"]]
        out: dict = {}
        for path, v in flat.items():
            at = out
            *objects, leaf = path.split(".")
            for name in objects:
                at = at.setdefault(name, {})
            at[leaf] = v
        return out


def _keyword(field: str, codes: np.ndarray, values, ndocs: int, shared: dict):
    """(term postings, keyword column) of a field with one value a
    document: only the values that occur are terms, sorted, as a refresh
    finds them."""
    from opensearch_tpu.index.segment import KeywordColumn, PostingsBlock
    counts = np.bincount(codes, minlength=len(values))
    seen = np.flatnonzero(counts)
    names = [values[c] for c in seen.tolist()]
    order = sorted(range(len(seen)), key=names.__getitem__)
    rank = np.full(len(values), -1, np.int32)
    rank[seen[order]] = np.arange(len(seen), dtype=np.int32)
    ords = rank[codes]
    vocab = [names[i] for i in order]
    if len(vocab) == 1:             # every document's: nothing to sort
        starts, doc_ids = np.asarray([0, ndocs], np.int64), shared["docs"]
    else:
        starts, doc_ids = _grouped(ords, len(vocab))
    block = PostingsBlock(field=field, vocab=vocab,
                          terms={v: i for i, v in enumerate(vocab)},
                          starts=starts, doc_ids=doc_ids,
                          tfs=shared["ones"])
    return block, KeywordColumn(field=field, vocab=vocab,
                                starts=shared["row_starts"], ords=ords,
                                doc_of_value=shared["docs"], min_ord=ords)


def _sorted_postings(term_ids: list, nterms: int):
    """Postings of the documents' tokens, one slot of the message an array:
    `term_ids[s][d]` is the term of slot s of document d. -> (starts
    i64[nterms + 1], doc_ids i32, tfs f32) by term, a document once a term
    with its tokens counted. One sort of packed (term, doc) keys."""
    ndocs = len(term_ids[0])
    keys = np.empty(len(term_ids) * ndocs, np.int64)
    docs = np.arange(ndocs, dtype=np.int64)
    for s, ids in enumerate(term_ids):
        part = keys[s * ndocs: (s + 1) * ndocs]
        part[:] = ids
        part <<= DOC_BITS
        part |= docs
    keys.sort()
    first = np.ones(len(keys), bool)
    np.not_equal(keys[1:], keys[:-1], out=first[1:])
    at = np.flatnonzero(first)
    tfs = np.diff(at, append=len(keys)).astype(np.float32)
    keys = keys[at]
    starts = np.zeros(nterms + 1, np.int64)
    np.cumsum(np.bincount(keys >> DOC_BITS, minlength=nterms),
              out=starts[1:])
    keys &= (1 << DOC_BITS) - 1
    return starts, keys.astype(np.int32), tfs


def _message_postings(events: dict, pool):
    """`PostingsBlock` of the analyzed `message` (the standard analyzer
    splits `Jan  2 03:04:05 ip-10-0-3-200 systemd: w1 .. w7` into 18
    lower-case tokens). The numerals (day, HH, MM, SS, four octets) and the
    words (process, seven dictionary words) are two sorts side by side,
    digits sorting under letters; `ip` and the month stand in every
    document. -> (block, document lengths i64)."""
    from opensearch_tpu.index.segment import PostingsBlock
    ts = events["ts_s"]
    ndocs = len(ts)
    # numerals: "0".."255" as they are, "00".."09" the zero-padded times
    numerals = sorted([str(v) for v in range(256)]
                      + [f"{v:02d}" for v in range(10)])
    row = {t: i for i, t in enumerate(numerals)}
    plain = np.asarray([row[str(v)] for v in range(256)], np.int16)
    padded = np.asarray([row[f"{v:02d}"] for v in range(60)], np.int16)
    tod = ts % 86400
    day = (ts - SPAN_START_S) // 86400 + 1          # all of January 2023
    octets = events["host_octets"][events["agent"]]
    numeral_slots = [plain[day], padded[tod // 3600],
                     padded[tod // 60 % 60], padded[tod % 60]] \
        + [plain[octets[:, k]] for k in range(4)]
    # words: the process names and the dictionary, sorted together
    codes, processes = events["kw"]["process.name"]
    alpha = sorted(set(processes) | set(events["dictionary"])
                   | {"ip", "jan"})
    arow = {t: i for i, t in enumerate(alpha)}
    prow = np.asarray([arow[p] for p in processes], np.int32)
    drow = np.asarray([arow[w] for w in events["dictionary"]], np.int32)
    word_slots = [prow[codes]] + [drow[events["words"][:, k]]
                                  for k in range(7)]
    assert len(numeral_slots) + len(word_slots) + 2 == MESSAGE_TOKENS
    num = pool.submit(_sorted_postings, numeral_slots, len(numerals))
    (wstarts, wdocs, wtfs) = _sorted_postings(word_slots, len(alpha))
    (nstarts, ndoc_ids, ntfs) = num.result()
    # `ip` and `jan` are every document's, once each
    df = np.diff(wstarts)
    for t in ("ip", "jan"):
        assert df[arow[t]] == 0, "a dictionary word shadows " + t
        df[arow[t]] = ndocs
    starts = np.zeros(len(numerals) + len(alpha) + 1, np.int64)
    starts[1: len(numerals) + 1] = nstarts[1:]
    np.cumsum(df, out=starts[len(numerals) + 1:])
    starts[len(numerals) + 1:] += nstarts[-1]
    doc_ids = np.empty(int(starts[-1]), np.int32)
    tfs = np.ones(int(starts[-1]), np.float32)
    doc_ids[: len(ndoc_ids)] = ndoc_ids
    tfs[: len(ntfs)] = ntfs
    base = len(numerals)
    cuts = sorted(arow[t] for t in ("ip", "jan")) + [len(alpha)]
    at, src = int(nstarts[-1]), 0
    prev = 0
    for cut in cuts:                # the sorted runs between the constants
        n = int(wstarts[cut] - wstarts[prev])
        doc_ids[at: at + n] = wdocs[src: src + n]
        tfs[at: at + n] = wtfs[src: src + n]
        at, src, prev = at + n, src + n, cut
        if cut < len(alpha):
            doc_ids[at: at + ndocs] = np.arange(ndocs, dtype=np.int32)
            at += ndocs
    assert at == len(doc_ids) and starts[base + len(alpha)] == at
    # only the terms that occur are rows, as a refresh finds them
    held = np.diff(starts) > 0
    vocab = [t for t, h in zip(numerals + alpha, held.tolist()) if h]
    starts = np.concatenate([starts[:1], starts[1:][held]])
    block = PostingsBlock(field="message", vocab=vocab,
                          terms={t: i for i, t in enumerate(vocab)},
                          starts=starts, doc_ids=doc_ids, tfs=tfs)
    return block, np.full(ndocs, MESSAGE_TOKENS, np.int64)


def plant_index(client, index: str, events: dict, settings: dict):
    """Create `index` through the client with the workload's mapping and
    plant one segment holding the 26 fields of `events`. -> the Segment."""
    from concurrent.futures import ThreadPoolExecutor

    from opensearch_tpu.index.segment import (CODEC_V2, NumericColumn,
                                              Segment, TextFieldStats,
                                              default_codec_version)
    client.indices.create(index, {"settings": settings, "mappings": MAPPING})
    svc = client.node.indices[index]
    ndocs = len(events["ts_s"])
    assert ndocs <= 1 << DOC_BITS
    present = np.ones(ndocs, bool)
    shared = {"docs": np.arange(ndocs, dtype=np.int32),
              "ones": np.ones(ndocs, np.float32),
              "row_starts": np.arange(ndocs + 1, dtype=np.int64)}

    def numeric(field, values):
        return NumericColumn(field=field, kind="int",
                             values=np.asarray(values, np.int64),
                             present=present)

    with ThreadPoolExecutor(8) as pool:
        text = pool.submit(_message_postings, events, pool)
        kw = {f: pool.submit(_keyword, f, np.asarray(codes), values, ndocs,
                             shared)
              for f, (codes, values) in events["kw"].items()}
        numeric_cols = {
            "@timestamp": numeric("@timestamp", events["ts_s"] * 1000),
            "event.ingested": numeric("event.ingested",
                                      events["ingested_ms"]),
            "aws.cloudwatch.ingestion_time": numeric(
                "aws.cloudwatch.ingestion_time", events["ingestion_ms"]),
            "metrics.size": numeric("metrics.size", events["size"]),
            "metrics.tmin": numeric("metrics.tmin", events["tmin"])}
        kw = {f: fut.result() for f, fut in kw.items()}
        message_pb, dl = text.result()
    postings = {f: pb for f, (pb, _col) in kw.items()}
    postings["message"] = message_pb
    seg = Segment(
        name="big5_0", ndocs=ndocs, postings=postings,
        numeric_cols=numeric_cols,
        keyword_cols={f: col for f, (_pb, col) in kw.items()}, geo_cols={},
        doc_lens={"message": dl},
        text_stats={"message": TextFieldStats(doc_count=ndocs,
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
