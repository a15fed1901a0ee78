"""The deployment kind `nested`: OpenSearch Benchmark's `nested` workload (a
StackOverflow dump in one index, a question a document, its answers a
nested object each) served as the search operations that read the nested
block: `randomized-nested-queries`, `randomized-sorted-term-queries` and
the two `randomized-nested-queries-with-inner-hits`. The answers are a
second row space on the device (a child `Segment` and a `parent` plane);
the `nested` clause is a child mask and a to-parent join inside the
request's one `executor_program`, the nested sort's key a resident plane,
the inner hits a launch over the page's blocks; none through the BM25
kernels.

What a reader of `README.md` needs, by member:

- `build`: first the program's counter groups this kind's metrics read are
  resolved at their home modules (`search.planes.NESTED_STATS` and
  `RANK_PLANE_STATS`, `search.compiler.EXECUTOR_STATS`);
  a program without them exits at once, naming them, before any data is
  made (such a program rebuilds a nested sort's key on the host a request
  and hands it over, 64 MiB at this size, and scores the whole child space
  a second time for inner hits and reads it back: it would not finish a
  window). Then `nested_questions.generate` draws the configuration's
  `ndocs` questions from its `corpus_seed` and `generator` (the collection
  is the deployment's fixed data set, like the other configurations';
  `--seed` orders the pool, samples the check and draws its fresh
  requests), the configuration's `cluster_settings`, where it has any, are
  put through the client, `plant_index` wraps the questions as one parent
  segment with its block of answers under an index created with the
  workload's mapping, and the device arrays of both row spaces are
  promoted and waited for. The read-out carries the rows of both spaces,
  the postings, the vocabularies' sizes and the device's bytes by field.
- `stream`: a traffic file's `generator` is a key of `GENERATORS`;
  `answers_rotation` deals the traffic file's `shapes` in rotation (`SHAPES`
  says what each is), each OSB's body. **The tag's rank by question count
  is drawn log-uniform over `tag_rank`**; the date of a `nested` clause
  uniform over the span of the answers' dates, on an even millisecond. No
  body comes twice. A twin is the same shape and tag with the date one
  millisecond on (odd: no draw's); of a `sorted_term`, which has no date,
  the same body with the sort's default `missing: _last` spelled out (the
  same compiled shapes, another key of the request cache). `weight` is the
  tag's questions. The stream notes the
  first requests it deals (the pool) in `built` for `hold`.
- `hold`: `nested_reference.Reference` over the run's own columns and its
  rule (`nested_control.py` is the control); it notes the window's shapes,
  in the order `run.py` sent them (the pool under `default_rng([seed,
  1])`'s permutation: the traced requests are the first of them), for
  `nested_roofline.py`, and its read-out adds the HBM ledger's bytes by
  tenant as they stand then.
- `counters`: the three counter groups, flat (`nested.join_updates`,
  `executor.params_h2d_bytes`, `sort.rank_plane.builds` ...)."""

from __future__ import annotations

import time

import numpy as np

import nested_questions as questions
import nested_reference as reference
import nested_roofline as roofline

# shape -> (size, nested clause, inner hits, nested sort)
SHAPES = {"nested": (10, True, None, None),
          "sorted_term": (10, False, None, {"mode": "max", "order": "desc"}),
          "inner_hits": (10, True, {"size": 3}, None),
          "inner_hits_big": (100, True, {"size": 100}, None)}
COUNTER_GROUPS = {
    "nested": ("planes", "NESTED_STATS", (
        "queries", "child_rows", "child_rows_real", "join_updates",
        "parents", "sort_plane_builds", "inner_hits_requests",
        "inner_hits_child_rows", "inner_hits_readback_bytes", "programs")),
    "sort.rank_plane": ("planes", "RANK_PLANE_STATS", ()),
    "executor": ("compiler", "EXECUTOR_STATS", ("launches",
                                                "params_h2d_bytes"))}


def _program() -> dict:
    """The program's counter groups by prefix; exits where the program
    lacks one."""
    import importlib
    groups, lacks = {}, []
    for prefix, (module, name, keys) in COUNTER_GROUPS.items():
        home = importlib.import_module("opensearch_tpu.search." + module)
        group = getattr(home, name, None)
        if group is None:
            lacks.append(f"search.{module}.{name} ({prefix}.*)")
            continue
        lacks += [f"{prefix}.{k}" for k in keys if k not in group]
        groups[prefix] = group
    if lacks:
        raise SystemExit(
            "benchmark: deployment kind 'nested' needs a program with the "
            f"counters {', '.join(lacks)}; this one has none (it rebuilds a "
            "nested sort's key on the host a request and hands it over, "
            "and scores the whole child space a second time for inner hits "
            "and reads it back)")
    return groups


def _host_gib() -> list:
    from pmc_articles import host_gib
    return [round(x, 3) for x in host_gib()]


def build(config: dict, seed: int, client, index: str) -> dict:
    import jax

    _program()
    t0, host = time.time(), {"start": _host_gib()}
    q = questions.generate(int(config["ndocs"]), int(config["corpus_seed"]),
                           config["generator"])
    generate_s, host["generate"] = time.time() - t0, _host_gib()
    if config.get("cluster_settings"):      # the deployment's own limits
        client.cluster.put_settings(config["cluster_settings"])
    seg = questions.plant_index(client, index, q, config["index_settings"])
    build_s, host["plant"] = time.time() - t0, _host_gib()

    t0 = time.time()
    jax.block_until_ready(seg.device_arrays())
    promote_s, host["promote"] = time.time() - t0, _host_gib()
    child = seg.nested[questions.PATH].child
    return {"questions": q, "seed": seed, "build_s": build_s,
            "promote_s": promote_s,
            "readout": {
                "rows": seg.ndocs, "rows_padded": seg.ndocs_pad,
                "child_rows": child.ndocs,
                "child_rows_padded": child.ndocs_pad,
                "generate_s": generate_s, "host_gib": host,
                "tag_values": int(len(q["tags"])),
                "postings": dict(
                    {f: pb.size for f, pb in seg.postings.items()},
                    **{f: pb.size for f, pb in child.postings.items()}),
                "vocabulary": dict(
                    {f: len(c.vocab) for f, c in seg.keyword_cols.items()},
                    **{f: len(c.vocab)
                       for f, c in child.keyword_cols.items()}),
                "device_bytes": _device_bytes(seg.device_arrays())}}


def _device_bytes(tree: dict, prefix: str = "") -> dict:
    """Bytes of a segment's device arrays by group and field, the child
    space's under `nested.<path>.`."""
    from opensearch_tpu.index.segment import _tree_nbytes
    out = {}
    for group, held in tree.items():
        if group == "nested":
            for path, child in held.items():
                out.update(_device_bytes(child, f"{prefix}nested.{path}."))
        elif isinstance(held, dict):
            out.update({f"{prefix}{group}.{f}": _tree_nbytes(a)
                        for f, a in held.items()})
        else:
            out[prefix + group] = int(held.nbytes)
    return out


class _Stream:
    def __init__(self, built: dict, traffic: dict, seed: int):
        p = traffic["params"]
        q = built["questions"]
        self.shapes = list(p["shapes"])
        self.ranks = [int(x) for x in p["tag_rank"]]
        self.names = q["tag_names"]
        self.df = questions.tag_question_counts(q)
        self.by_rank = np.argsort(-self.df, kind="stable")
        self.rank = np.empty(len(self.df), np.int64)
        self.rank[self.by_rank] = np.arange(len(self.df))
        assert self.df[self.by_rank[self.ranks[1] - 1]] > 0, \
            "the traffic's tag ranks reach past the tags that occur"
        # the span of the answers' dates, on even milliseconds
        self.dates = (int(q["ans_date_ms"].min()) // 2 * 2,
                      int(q["ans_date_ms"].max()) // 2 * 2)
        self._seen, self._turn, self._built = set(), 0, built
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self._rng = np.random.default_rng([seed, 2])

    def _spec(self, shape: str, tag: int, date_ms, twin=False) -> dict:
        size, clause, inner, sort = SHAPES[shape]
        if twin and not clause:     # the default spelled out: another body
            sort = dict(sort, missing="_last")
        spec = {"shape": shape, "tag": int(tag), "size": size,
                "child": {"date_lte_ms": int(date_ms)} if clause else None,
                "inner": inner, "sort": sort,
                "weight": int(self.df[tag])}
        spec["body"] = reference.body(spec, self.names, questions.user_name)
        self._seen.add((shape, int(tag), date_ms))
        return spec

    def take(self, n: int) -> list:
        out, rng = [], self._rng
        lo, hi = np.log(self.ranks[0]), np.log(self.ranks[1] + 1)
        while len(out) < n:
            shape = self.shapes[self._turn % len(self.shapes)]
            tag = int(self.by_rank[int(np.exp(rng.uniform(lo, hi))) - 1])
            date = int(rng.integers(self.dates[0] // 2,
                                    self.dates[1] // 2 + 1)) * 2 \
                if SHAPES[shape][1] else None
            if (shape, tag, date) in self._seen:
                continue
            self._turn += 1
            out.append(self._spec(shape, tag, date))
        self._built.setdefault("pool", out)     # the first dealt: the pool
        return out

    def twin(self, spec: dict) -> dict:
        """The same shape and tag with the date one millisecond on (odd: no
        draw's); of a request without a date, the same body with the
        sort's default `missing` spelled out."""
        date = spec["child"]["date_lte_ms"] + 1 \
            if spec["child"] is not None else None
        return self._spec(spec["shape"], spec["tag"], date, twin=True)


# the request generators a traffic file of this kind may name
GENERATORS = {"answers_rotation": _Stream}


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    name = traffic["generator"]
    if name not in GENERATORS:
        raise SystemExit(f"benchmark: deployment kind 'nested' has no "
                         f"request generator {name!r} "
                         f"(has {sorted(GENERATORS)})")
    return GENERATORS[name](built, traffic, seed)


def reference_of(built: dict, config: dict) -> reference.Reference:
    """The run's reference, made once."""
    if "reference" not in built:
        built["reference"] = reference.Reference(
            built["questions"], k1=config["guarantees"]["bm25_k1"])
    return built["reference"]


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    """(spec, response) pairs held to the reference by its rule; the
    read-out also says what the device holds now, after warm-up and
    window: the ledger's bytes by tenant."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    out = reference.hold(held, reference_of(built, config))
    q, pool = built["questions"], built["pool"]
    sent = np.random.default_rng([built["seed"], 1]).permutation(len(pool))
    roofline.note_window(int(q["ans_off"][-1]), len(q["created_ms"]),
                         [pool[r]["child"] is not None for r in sent])
    out["residency"] = {
        "hbm_ledger_bytes": {k: t["bytes"] for k, t in
                             LEDGER.snapshot()["tenants"].items()}}
    return out


def counters(client) -> dict:
    return {f"{prefix}.{k}": v
            for prefix, group in _program().items()
            for k, v in group.items()}
