"""The deployment kind `pmc`: OpenSearch Benchmark's `pmc` workload
(full-text articles of PubMed Central in one index) served as its `phrase`
operation: `match_phrase` over the `body`'s positional postings, which live
on the device as two planes of the segment; the join, one scatter-add, the
tf curve and a top-k through `programs.run_segment`'s `executor_program`,
none through the BM25 kernels.

What a reader of `README.md` needs, by member:

- `build`: first the program's counter groups this kind's metrics read are
  imported from their home module (`search.compiler`: `EXECUTOR_STATS`,
  `PHRASE_STATS`) with the shape function a twin is found by
  (`ops.positions.phrase_shape`); a program without them exits at once,
  naming them, before any data is made (such a program builds every term of
  every phrase on the host and hands it over, tens of MiB a request, and
  anchors a phrase on its first word whatever that costs: it would not
  finish a window). Then `pmc_articles.generate` draws the configuration's
  `ndocs` articles from its `corpus_seed` and `generator` (the collection
  is the deployment's fixed data set, like the other configurations';
  `--seed` orders the pool, samples the check and draws its fresh
  requests), the configuration's `cluster_settings`, where it has any, are
  put through the client, `plant_index` inverts the token stream and wraps
  the nine fields as one segment under an index created with the
  workload's mapping, and the segment's device arrays, the positional
  planes with them, are promoted and waited for. The read-out carries the
  documents, tokens, postings, distinct terms and the device's bytes by
  field and plane.
- `stream`: a traffic file's `generator` is a key of `GENERATORS`;
  `phrase_rotation` deals the traffic file's `shapes` in rotation, each a
  phrase of the generator's collocation table in OSB's `phrase` body
  (`{"query": {"match_phrase": {"body": "<words>"}}}`, no `size`). **The
  rarest word's collection-frequency rank is drawn log-uniform over
  `rarest_rank`** and the phrase dealt is one whose rarest word IS the
  term of that rank: it leads, and the table gives the other words
  (`phrase2`: a partner; `phrase3`: a partner and the partner's partner;
  `phrase3_common`: a partner among the `common` most frequent terms, then
  its partner), each commoner than the first or the first again. Where the
  term of the drawn rank leads no such phrase that has not been dealt
  (its partners are all rarer; it has no common partner), its nearest
  neighbour in rank that does takes its place. No phrase comes twice. A
  twin is another phrase of the same shape, under the same law, whose
  terms fall in the same program shape (`phrase_shape` of the terms'
  position counts: the anchor's bucket and the searches' depth), found by
  walking the first word's neighbours in rank. `weight` is the rarest
  word's positions. The stream notes the first phrases it deals (the pool)
  in `built` for `hold`.
- `hold`: `pmc_reference.Reference` over the run's own token stream and
  `bm25_match`'s rule under the configuration's `score_rtol`
  (`pmc_control.py` is the control); it notes the window's phrases, in the
  order `run.py` sent them (the pool under `default_rng([seed, 1])`'s
  permutation: the traced requests are the first of them), for
  `pmc_roofline.py`, and its read-out adds the HBM ledger's bytes by tenant
  as they stand then.
- `counters`: the two counter groups, flat (`executor.launches`,
  `phrase.probe_elems` ...)."""

from __future__ import annotations

import time

import numpy as np

import pmc_articles as articles
import pmc_reference as reference
import pmc_roofline as roofline

FIELD = "body"


def _program() -> dict:
    """The program's counter groups by prefix, and its shape function;
    exits where the program has none."""
    try:
        from opensearch_tpu.ops.positions import phrase_shape
        from opensearch_tpu.search.compiler import (EXECUTOR_STATS,
                                                    PHRASE_STATS)
    except ImportError as e:
        raise SystemExit(
            "benchmark: deployment kind 'pmc' needs a program whose "
            "positions are resident planes of the segment "
            "(search.compiler.PHRASE_STATS, ops.positions.phrase_shape); "
            f"this one has none ({e}): it builds every phrase term on the "
            "host and hands it over a request")
    return {"groups": {"executor": EXECUTOR_STATS, "phrase": PHRASE_STATS},
            "phrase_shape": phrase_shape}


def _host_gib() -> list:
    """[resident now, peak so far] of the process, GiB (the chip machine
    ends a command at 40 GiB; the runtime holds a share of it before any
    data is made, and the token stream, the postings and the planes' host
    copies are most of the rest)."""
    return [round(x, 3) for x in articles.host_gib()]


def build(config: dict, seed: int, client, index: str) -> dict:
    import jax

    _program()
    t0, host = time.time(), {"start": _host_gib()}
    arts = articles.generate(int(config["ndocs"]), int(config["corpus_seed"]),
                             config["generator"])
    generate_s, host["generate"] = time.time() - t0, _host_gib()
    if config.get("cluster_settings"):      # the deployment's own limits
        client.cluster.put_settings(config["cluster_settings"])
    seg = articles.plant_index(client, index, arts, config["index_settings"])
    build_s, host["plant"] = time.time() - t0, _host_gib()

    t0 = time.time()
    jax.block_until_ready(seg.device_arrays())
    planes = seg.device_positions(FIELD)
    jax.block_until_ready(planes)
    promote_s, host["promote"] = time.time() - t0, _host_gib()
    pb = seg.postings[FIELD]
    return {"articles": arts, "seed": seed, "build_s": build_s,
            "promote_s": promote_s,
            "readout": {
                "rows": seg.ndocs, "rows_padded": seg.ndocs_pad,
                "generate_s": generate_s, "host_gib": host,
                "tokens": int(len(arts["tok"])),
                "postings": {f: p.size for f, p in seg.postings.items()},
                "distinct_terms": pb.nterms,
                "position_slots": int(planes["doc"].shape[0]),
                "device_bytes": dict(
                    _device_bytes(seg.device_arrays()),
                    **{f"positions.{FIELD}.{k}": int(v.nbytes)
                       for k, v in planes.items()})}}


def _device_bytes(tree: dict) -> dict:
    """Bytes of a segment's device arrays by group and field."""
    from opensearch_tpu.index.segment import _tree_nbytes
    out = {}
    for group, held in tree.items():
        if isinstance(held, dict):
            out.update({f"{group}.{f}": _tree_nbytes(a)
                        for f, a in held.items()})
        else:
            out[group] = int(held.nbytes)
    return out


class _Stream:
    def __init__(self, built: dict, traffic: dict, seed: int):
        p = traffic["params"]
        arts = built["articles"]
        self.shapes = list(p["shapes"])
        self.ranks = [int(x) for x in p["rarest_rank"]]
        self.table, self.words = arts["table"], arts["words"]
        self.cf = articles.collection_frequency(arts)
        self.by_rank = np.argsort(-self.cf, kind="stable")
        self.rank = np.empty(len(self.cf), np.int64)
        self.rank[self.by_rank] = np.arange(len(self.cf))
        self.common = set(self.by_rank[: int(p["common"])].tolist())
        self.phrase_shape = _program()["phrase_shape"]
        self._seen, self._turn, self._built = set(), 0, built
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self._rng = np.random.default_rng([seed, 2])

    # -- phrases of the table ---------------------------------------------

    def _follow(self, shape: str, first: int, picks) -> tuple:
        """The phrase of `shape` that begins with `first` and follows the
        table by `picks` (a partner index a step); None where the table
        has none (a `phrase3_common` whose first word has no common
        partner)."""
        if shape == "phrase3_common":
            mids = [int(t) for t in self.table[first] if int(t) in self.common]
            if not mids:
                return None
            mid = mids[picks[0] % len(mids)]
            return first, mid, int(self.table[mid, picks[1]])
        terms = [first]
        for k in picks[: 1 if shape == "phrase2" else 2]:
            terms.append(int(self.table[terms[-1], k]))
        return tuple(terms)

    def _wanted(self, terms) -> bool:
        """The first word the phrase's rarest (the others commoner, or the
        first again), its rank inside the traffic's bounds, the phrase not
        yet dealt."""
        if terms is None or terms in self._seen:
            return False
        rarest = max(int(self.rank[t]) for t in terms)
        return rarest == int(self.rank[terms[0]]) \
            and self.ranks[0] <= rarest <= self.ranks[1]

    def _led_by(self, shape: str, at: int, order) -> tuple:
        """A wanted phrase of `shape` whose rarest word is the term of rank
        `at`, or of its nearest neighbour in rank that leads one; the
        table's partners tried in `order`."""
        k = self.table.shape[1]
        for step in range(len(self.by_rank)):
            for r in dict.fromkeys((at + step, at - step)):
                if not self.ranks[0] <= r <= self.ranks[1]:
                    continue
                for pick in order:
                    terms = self._follow(shape, int(self.by_rank[r]),
                                         divmod(int(pick), k))
                    if self._wanted(terms):
                        return terms
        raise SystemExit(f"benchmark: no {shape} left whose rarest word's "
                         f"rank lies in {self.ranks}")

    def _key(self, terms) -> tuple:
        """The program shape an exact phrase of `terms` falls in: the
        anchor is the term of fewest positions (the first where tied)."""
        lens = [int(self.cf[t]) for t in terms]
        anchor = min(range(len(lens)), key=lambda i: (lens[i], i))
        return (len(terms),) + tuple(self.phrase_shape(
            [lens[anchor]] + lens[:anchor] + lens[anchor + 1:]))

    def _spec(self, shape: str, terms: tuple) -> dict:
        self._seen.add(terms)
        text = " ".join(self.words[t] for t in terms)
        return {"shape": shape, "terms": list(terms),
                "body": {"query": {"match_phrase": {FIELD: text}}},
                "weight": int(min(self.cf[t] for t in terms))}

    def take(self, n: int) -> list:
        out, rng = [], self._rng
        lo, hi = np.log(self.ranks[0]), np.log(self.ranks[1])
        k = self.table.shape[1]
        while len(out) < n:
            shape = self.shapes[self._turn % len(self.shapes)]
            at = int(np.exp(rng.uniform(lo, hi)))   # the rarest word's rank
            terms = self._led_by(shape, at, rng.permutation(k * k))
            self._turn += 1
            out.append(self._spec(shape, terms))
        self._built.setdefault("pool", out)     # the first dealt: the pool
        return out

    def twin(self, spec: dict) -> dict:
        """Another phrase of the spec's shape and program shape: the first
        one wanted among the phrases of the first word's neighbours in
        rank, nearest first."""
        key, k = self._key(spec["terms"]), self.table.shape[1]
        at = int(self.rank[spec["terms"][0]])
        for step in range(1, len(self.by_rank)):
            for r in (at + step, at - step):
                if not 0 <= r < len(self.by_rank):
                    continue
                first = int(self.by_rank[r])
                for picks in np.ndindex(k, k):
                    terms = self._follow(spec["shape"], first, picks)
                    if self._wanted(terms) and self._key(terms) == key:
                        return self._spec(spec["shape"], terms)
        raise SystemExit(f"benchmark: no twin for {spec['terms']}")


# the request generators a traffic file of this kind may name
GENERATORS = {"phrase_rotation": _Stream}


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    name = traffic["generator"]
    if name not in GENERATORS:
        raise SystemExit(f"benchmark: deployment kind 'pmc' has no request "
                         f"generator {name!r} (has {sorted(GENERATORS)})")
    return GENERATORS[name](built, traffic, seed)


def reference_of(built: dict, config: dict) -> reference.Reference:
    """The run's reference, made once."""
    if "reference" not in built:
        g, arts = config["guarantees"], built["articles"]
        built["reference"] = reference.Reference(
            arts["tok"], arts["offsets"], arts["live"], k1=g["bm25_k1"],
            b=g["bm25_b"], threads=articles.threads())
    return built["reference"]


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    """(spec, response) pairs held to the reference by its rule; the
    read-out also says what the device holds now, after warm-up and
    window: the ledger's bytes by tenant."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    g = config["guarantees"]
    ref = reference_of(built, config)
    out = reference.hold(held, ref, float(g["score_rtol"]), int(g["page"]))
    pool = built["pool"]
    sent = np.random.default_rng([built["seed"], 1]).permutation(len(pool))
    roofline.note_window(ref, [pool[r]["terms"] for r in sent])
    out["residency"] = {
        "hbm_ledger_bytes": {k: t["bytes"] for k, t in
                             LEDGER.snapshot()["tenants"].items()}}
    return out


def counters(client) -> dict:
    return {f"{prefix}.{k}": v
            for prefix, group in _program()["groups"].items()
            for k, v in group.items()}
