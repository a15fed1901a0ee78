"""The deployment kind `big5`: OpenSearch Benchmark's `big5` workload (ECS
log events in one index) served as its Terms Aggregation operations and the
two `cardinality-agg` operations beside them: `terms`, `multi_terms`,
`composite` and `cardinality` over keyword ordinals, all through the column
executor (`compiler.run_segment`'s `executor_program`), none through the
BM25 kernels.

What a reader of `README.md` needs, by member:

- `build`: first the program's counters this kind's metrics read are
  resolved (`compiler.EXECUTOR_STATS` with `launches`, `AGG_STATS` with
  `terms.ordinals`, `terms.records` and `composite.combinations`,
  `BUCKET_PLANE_STATS`); a program without them exits at once, naming
  them, before any data is made (such a program also refuses a composite
  over three keyword sources, builds a Python record a vocabulary entry a
  request and hands every `multi_terms` launch a host array of a value a
  row: it would not hold the rule, nor finish a window). Then
  `big5_events.generate` makes the configuration's `ndocs` events from its
  `corpus_seed` and `generator` (the collection is the deployment's fixed
  data set, like the other configurations'; `--seed` orders the pool,
  samples the check and draws its fresh requests), the configuration's
  `cluster_settings`, where it has any, are put through the client,
  `plant_index` wraps the events as one segment with all 26 fields under an
  index created with the workload's mapping, and the segment's device
  arrays are promoted and waited for. The read-out carries the rows, the
  postings, the vocabularies' sizes and the device arrays' bytes by field.
- `stream`: a traffic file's `generator` is a key of `GENERATORS`;
  `terms_rotation` deals the seven operations in a fixed rotation (`SHAPES`
  of `big5_reference.py`), each OSB's body under a range on `@timestamp`
  whose bounds are drawn: for the three operations OSB runs under a range,
  the length log-uniform between the traffic file's `window_hours`, for the
  four it runs under `match_all` uniform between `span_share` of the
  corpus's span; the start uniform with the window inside the span; both
  on an even second, as ISO-8601 strings (`gte` / `lt`). A twin moves the
  upper bound by one second (odd, so no draw's body): the same compiled
  shapes, another body. `weight` is the window's length in hours.
- `hold`: `big5_reference.Reference` over the run's own columns and its
  exact rule, every limit 0 (`big5_control.py` is the control); its
  read-out adds the HBM ledger's bytes by tenant as they stand then.
- `counters`: the three counter groups, flat (`executor.launches`,
  `aggs.terms.records`, `aggs.bucket_plane.builds` ...)."""

from __future__ import annotations

import time

import numpy as np

import big5_events as events
import big5_reference as reference

COUNTER_GROUPS = {"executor": ("EXECUTOR_STATS", ("launches",
                                                  "params_h2d_bytes")),
                  "aggs": ("AGG_STATS", ("scatter.updates", "blocked.rows",
                                         "terms.ordinals", "terms.records",
                                         "composite.combinations")),
                  "aggs.bucket_plane": ("BUCKET_PLANE_STATS", ()),
                  "sort.rank_plane": ("RANK_PLANE_STATS", ())}
RANGED = ("multi_terms-keyword", "composite-terms", "composite_terms-keyword")


def _counter_groups() -> dict:
    """prefix -> the program's counter group; exits where a group or one
    of the counters this kind reads is missing."""
    from opensearch_tpu.search import compiler
    groups, lacks = {}, []
    for prefix, (name, keys) in COUNTER_GROUPS.items():
        group = getattr(compiler, name, None)
        if group is None:
            lacks.append(f"compiler.{name} ({prefix}.*)")
            continue
        lacks += [f"{prefix}.{k}" for k in keys if k not in group]
        groups[prefix] = group
    if lacks:
        raise SystemExit(
            "benchmark: deployment kind 'big5' needs a program with the "
            f"counters {', '.join(lacks)}; this one has none (it refuses a "
            "composite whose sources' value spaces multiply past 2^22, "
            "builds a record a vocabulary entry a request and hands a "
            "multi_terms launch a host array of ndocs_pad elements)")
    return groups


def build(config: dict, seed: int, client, index: str) -> dict:
    import jax

    _counter_groups()
    t0 = time.time()
    columns = events.generate(int(config["ndocs"]),
                              int(config["corpus_seed"]),
                              config["generator"])
    generate_s = time.time() - t0
    if config.get("cluster_settings"):      # the deployment's own limits
        client.cluster.put_settings(config["cluster_settings"])
    seg = events.plant_index(client, index, columns,
                             config["index_settings"])
    build_s = time.time() - t0

    t0 = time.time()
    jax.block_until_ready(seg.device_arrays())
    promote_s = time.time() - t0
    return {"columns": columns, "build_s": build_s, "promote_s": promote_s,
            "readout": {
                "rows": seg.ndocs, "rows_padded": seg.ndocs_pad,
                "generate_s": generate_s,
                "postings": {f: pb.size for f, pb in seg.postings.items()},
                "vocabulary": {f: len(c.vocab)
                               for f, c in seg.keyword_cols.items()},
                "device_bytes": _device_bytes(seg.device_arrays())}}


def _spec(shape: str, lo_s: int, hi_s: int) -> dict:
    """The operation `shape` over [lo_s, hi_s) (epoch seconds)."""
    body = {"size": 0,
            "query": {"range": {"@timestamp": {
                "gte": events.iso_seconds(lo_s),
                "lt": events.iso_seconds(hi_s)}}},
            "aggs": reference.agg_body(shape)}
    return {"shape": shape, "lo_s": lo_s, "hi_s": hi_s, "page": 0,
            "body": body, "weight": (hi_s - lo_s) / 3600.0}


class _Stream:
    def __init__(self, built: dict, traffic: dict, seed: int):
        p = traffic["params"]
        self.hours = [float(x) for x in p["window_hours"]]
        self.share = [float(x) for x in p["span_share"]]
        self._seen, self._turn = set(), 0
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self._rng = np.random.default_rng([seed, 2])

    def _draw(self, shape: str) -> tuple:
        """(lo_s, hi_s) of one body: even seconds inside the span."""
        rng, span = self._rng, events.SPAN_S
        if shape in RANGED:
            lo_h, hi_h = self.hours
            length = np.exp(rng.uniform(np.log(lo_h * 3600),
                                        np.log(hi_h * 3600)))
        else:
            length = rng.uniform(self.share[0], self.share[1]) * span
        length = min(max(int(length) // 2 * 2, 2), span)
        start = int(rng.integers(0, (span - length) // 2 + 1)) * 2
        return (events.SPAN_START_S + start,
                events.SPAN_START_S + start + length)

    def take(self, n: int) -> list:
        out = []
        while len(out) < n:
            shape = reference.SHAPES[self._turn % len(reference.SHAPES)]
            lo, hi = self._draw(shape)
            if (shape, lo, hi) in self._seen:
                continue
            self._seen.add((shape, lo, hi))
            self._turn += 1
            out.append(_spec(shape, lo, hi))
        return out

    def twin(self, spec: dict) -> dict:
        """The upper bound one second on (odd: no draw's)."""
        return _spec(spec["shape"], spec["lo_s"], spec["hi_s"] + 1)


# the request generators a traffic file of this kind may name
GENERATORS = {"terms_rotation": _Stream}


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    name = traffic["generator"]
    if name not in GENERATORS:
        raise SystemExit(f"benchmark: deployment kind 'big5' has no "
                         f"request generator {name!r} "
                         f"(has {sorted(GENERATORS)})")
    return GENERATORS[name](built, traffic, seed)


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


def reference_of(built: dict) -> reference.Reference:
    """The run's reference, made once (a field's key order is built on the
    first request that names it and kept)."""
    if "reference" not in built:
        built["reference"] = reference.Reference(built["columns"])
    return built["reference"]


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    """(spec, response) pairs held to the reference by its rule; the
    read-out also says what the device holds now, after warm-up and
    window: the ledger's bytes by tenant and the planes' counters."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    out = reference.hold(held, reference_of(built))
    out["residency"] = {
        "hbm_ledger_bytes": {k: t["bytes"] for k, t in
                             LEDGER.snapshot()["tenants"].items()},
        "planes": {k: v for k, v in counters(None).items()
                   if "plane" in k}}
    return out


def counters(client) -> dict:
    return {f"{prefix}.{k}": v for prefix, group in _counter_groups().items()
            for k, v in group.items()}
