"""The deployment kind `http_logs`: OpenSearch Benchmark's `http_logs`
workload (web-server log events in a time-series index) served as its
search operations: time-range filters, an hourly `date_histogram` and
field sorts, all through the column executor (`compiler.run_segment`'s
`executor_program`), none through the BM25 kernels.

What a reader of `README.md` needs, by member:

- `build`: first the program's counters this kind's metrics read are
  resolved (`compiler.EXECUTOR_STATS`, `BUCKET_PLANE_STATS`,
  `RANK_PLANE_STATS`); a program without them exits at once, naming them,
  before any data is made (such a program also walks a 49M-row column in
  Python and ships a 256 MiB array a request: it would not finish a run).
  Then `http_logs_events.generate` makes the configuration's `ndocs` events
  from its `corpus_seed` and `generator` (the collection is the
  deployment's fixed data set, like the other configurations'; `--seed`
  orders the pool, samples the check and draws its fresh requests), the
  configuration's `cluster_settings` are put through the client,
  `plant_index` wraps them as one segment with all five fields under an
  index created with the workload's mapping, and the segment's device
  arrays are promoted and waited for. The read-out carries the rows, the
  postings and the device arrays' bytes by field.
- `stream`: a traffic file's `generator` is a key of `GENERATORS`;
  `dashboard_rotation` deals the eight operations in a fixed rotation (`SHAPES` of
  `http_logs_reference.py`), each OSB's body with its time bounds drawn:
  the window's length log-uniform between the traffic file's
  `min_window_s` and `max_window_s` in whole seconds, its start uniform
  with the window inside the collection's span, on an even second, both
  bounds as ISO-8601 strings (`gte` / `lt`). A twin moves the upper bound
  by one second (odd, so no draw's body): the same compiled shapes,
  another body. `weight` is the window's length in hours.
- `hold`: `http_logs_reference.Reference` over the run's own columns and
  its exact rule, every limit 0 (`http_logs_control.py` is the control);
  its read-out adds the HBM ledger's bytes by tenant as they stand then.
- `counters`: the three counter groups, flat (`executor.params_h2d_bytes`,
  `aggs.bucket_plane.builds` ...), for `params_h2d_mib_per_query`."""

from __future__ import annotations

import time

import numpy as np

import http_logs_events as events
import http_logs_reference as reference

COUNTER_GROUPS = {"executor": "EXECUTOR_STATS",
                  "aggs.bucket_plane": "BUCKET_PLANE_STATS",
                  "sort.rank_plane": "RANK_PLANE_STATS"}


def _counter_groups() -> dict:
    """prefix -> the program's counter group; exits where one is missing."""
    from opensearch_tpu.search import compiler
    groups = {p: getattr(compiler, name, None)
              for p, name in COUNTER_GROUPS.items()}
    lacks = [f"compiler.{COUNTER_GROUPS[p]} ({p}.*)"
             for p, g in groups.items() if g is None]
    if lacks:
        raise SystemExit(
            "benchmark: deployment kind 'http_logs' needs a program "
            f"with the counters {', '.join(lacks)}; this one has none "
            "(it binds a date_histogram by a per-row Python walk and a "
            "host array of ndocs_pad elements a request)")
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
                "device_bytes": _device_bytes(seg.device_arrays())}}


def _spec(shape: str, lo_s: int, hi_s: int, page: int) -> dict:
    """The operation `shape` over [lo_s, hi_s) (epoch seconds)."""
    window = {"range": {"@timestamp": {"gte": events.iso_seconds(lo_s),
                                       "lt": events.iso_seconds(hi_s)}}}
    if shape == "range":
        body = {"query": window}
    elif shape.endswith("s-in-range"):
        body = {"query": {"bool": {"must": [
            window, {"match": {"status": shape[:3]}}]}}}
    elif shape == "hourly_agg":
        body = {"size": 0, "query": window, "aggs": {reference.AGG_NAME: {
            "date_histogram": {"field": "@timestamp",
                               "calendar_interval": "hour"}}}}
    else:
        order, _sort, field = shape.split("_")
        body = {"query": window, "size": page,
                "sort": [{reference.SORT_FIELD[field]: order}]}
    return {"shape": shape, "lo_ms": lo_s * 1000, "hi_ms": hi_s * 1000,
            "page": page, "body": body, "weight": (hi_s - lo_s) / 3600.0}


class _Stream:
    def __init__(self, built: dict, traffic: dict, seed: int):
        ts = built["columns"]["ts_ms"]
        self.lo_s, self.hi_s = int(ts[0]) // 1000, int(ts[-1]) // 1000 + 1
        p = traffic["params"]
        self.min_w = int(p["min_window_s"])
        self.max_w = min(int(p["max_window_s"]), self.hi_s - self.lo_s - 2)
        self.page = int(traffic["size"])
        self._seen, self._turn = set(), 0
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self._rng = np.random.default_rng([seed, 2])

    def take(self, n: int) -> list:
        out = []
        while len(out) < n:
            shape = reference.SHAPES[self._turn % len(reference.SHAPES)]
            length = 2 * max(int(np.exp(self._rng.uniform(
                np.log(self.min_w), np.log(self.max_w)))) // 2, 1)
            start = self.lo_s + 2 * int(self._rng.integers(
                0, (self.hi_s - self.lo_s - length) // 2))
            if (shape, start, length) in self._seen:
                continue
            self._seen.add((shape, start, length))
            self._turn += 1
            out.append(_spec(shape, start, start + length, self.page))
        return out

    def twin(self, spec: dict) -> dict:
        """The upper bound one second on: odd, so no draw's body."""
        return _spec(spec["shape"], spec["lo_ms"] // 1000,
                     spec["hi_ms"] // 1000 + 1, spec["page"])


# the request generators a traffic file of this kind may name
GENERATORS = {"dashboard_rotation": _Stream}


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    name = traffic["generator"]
    if name not in GENERATORS:
        raise SystemExit(f"benchmark: deployment kind 'http_logs' has "
                         f"no request generator {name!r} "
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


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    """(spec, response) pairs held to the reference by its rule; the
    read-out also says what the device holds now, after warm-up and
    window: the ledger's bytes by tenant and the planes' counters."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    c = built["columns"]
    out = reference.hold(held, reference.Reference(
        c["ts_ms"], c["status"], c["size"]))
    out["residency"] = {
        "hbm_ledger_bytes": {k: t["bytes"] for k, t in
                             LEDGER.snapshot()["tenants"].items()},
        "planes": {k: v for k, v in counters(None).items()
                   if "plane" in k}}
    return out


def counters(client) -> dict:
    return {f"{prefix}.{k}": v for prefix, group in _counter_groups().items()
            for k, v in group.items()}
