"""The deployment kind `vectorsearch`: OpenSearch Benchmark's `vectorsearch`
workload (passage embeddings in one `knn_vector` field) served as its search
half: approximate k-NN through the `knn` query (`compiler.emit`'s `knn`:
the IVF probe of `ops/ann.py`, the only stage of the system that is a
matrix product), the plan's top-k and a fetch of `k` ids.

What a reader of `README.md` needs, by member:

- `build`: first the program's counters this kind's metrics read are
  resolved (`compiler.KNN_STATS`, `ops.ann.IVF_STATS`,
  `compiler.EXECUTOR_STATS`); a program without them exits at once, naming
  them, before any data is made (such a program also scores by a float32
  product that names no precision, one bfloat16 pass on the chip: it would
  not hold `score_rtol`). Then `vectorsearch_vectors.generate` makes the
  configuration's `ndocs` vectors from its `corpus_seed` and `generator`
  (the collection is the deployment's fixed data set, like the other
  configurations'; `--seed` orders the pool, samples the check and draws
  its fresh queries), `plant_index` wraps them as one segment under an
  index created with the workload's mapping, and the segment's device
  arrays, the IVF lists among them, are promoted and waited for. The
  read-out carries the rows, the device arrays' bytes and the build's
  counters (`ivf`: seconds, rows, spilled rows, nlist, cap).
- `stream`: a traffic file's `generator` is a key of `GENERATORS`;
  `held_out` draws query vectors from the corpus's own mixture (held-out
  passages, never rows of the corpus) and wraps each in OSB's body. A twin
  is another held-out vector; no body comes twice. `weight` is the
  vector's length.
- `hold`: `vectorsearch_reference.Reference` over the run's own vectors
  and its rule (`vectorsearch_control.py` is the control); its read-out
  adds the HBM ledger's bytes by tenant as they stand then.
- `counters`: the three counter groups, flat (`knn.candidate_slots`,
  `ivf.build_s`, `executor.params_h2d_bytes` ...)."""

from __future__ import annotations

import time

import numpy as np

import vectorsearch_reference as reference
import vectorsearch_vectors as vectors

# prefix -> (module of the program, its counter group)
COUNTER_GROUPS = {"knn": ("search.compiler", "KNN_STATS"),
                  "ivf": ("ops.ann", "IVF_STATS"),
                  "executor": ("search.compiler", "EXECUTOR_STATS")}


def _counter_groups() -> dict:
    """prefix -> the program's counter group; exits where one is missing."""
    import importlib
    groups = {p: getattr(importlib.import_module("opensearch_tpu." + mod),
                         name, None)
              for p, (mod, name) in COUNTER_GROUPS.items()}
    lacks = ["{}.{} ({}.*)".format(*COUNTER_GROUPS[p], p)
             for p, g in groups.items() if g is None]
    if lacks:
        raise SystemExit(
            "benchmark: deployment kind 'vectorsearch' needs a program "
            f"with the counters {', '.join(lacks)}; this one has none (it "
            "scores a knn query by a float32 product that names no "
            "precision, which the chip runs as one bfloat16 pass)")
    return groups


def build(config: dict, seed: int, client, index: str) -> dict:
    import jax

    groups = _counter_groups()
    t0 = time.time()
    corpus = vectors.generate(int(config["ndocs"]),
                              int(config["corpus_seed"]),
                              config["generator"])
    generate_s = time.time() - t0
    seg = vectors.plant_index(client, index, corpus, config)
    build_s = time.time() - t0

    t0 = time.time()
    jax.block_until_ready(seg.device_arrays())
    promote_s = time.time() - t0
    return {"corpus": corpus, "build_s": build_s, "promote_s": promote_s,
            "readout": {
                "rows": seg.ndocs, "rows_padded": seg.ndocs_pad,
                "generate_s": generate_s, "ivf": dict(groups["ivf"].items()),
                "device_bytes": _device_bytes(seg.device_arrays())}}


def _spec(vector: np.ndarray, traffic: dict, key: tuple) -> dict:
    k = int(traffic["params"]["k"])
    body = dict(traffic["params"]["query_body"], size=int(traffic["size"]),
                query={"knn": {vectors.MAPPING_FIELD: {
                    "vector": vector.tolist(), "k": k}}})
    return {"vector": vector, "key": key, "body": body,
            "weight": float(np.linalg.norm(vector))}


class _Stream:
    """Held-out draws of the corpus's mixture, each from its own generator
    keyed by (corpus_seed, stream, seed, serial): the `serial`-th query of
    a seed and its twin are two different vectors, and no key comes
    twice."""

    def __init__(self, built: dict, traffic: dict, seed: int):
        self.corpus, self.traffic = built["corpus"], traffic
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self.seed, self._serial = int(seed), 0

    def _draw(self, stream: int, seed: int, serial: int) -> dict:
        c = self.corpus
        return _spec(vectors.query_vector(c["mixture"], c["corpus_seed"],
                                          seed, stream, serial),
                     self.traffic, (stream, seed, serial))

    def take(self, n: int) -> list:
        out = [self._draw(vectors.QUERY_STREAM, self.seed, self._serial + i)
               for i in range(n)]
        self._serial += n
        return out

    def twin(self, spec: dict) -> dict:
        _stream, seed, serial = spec["key"]
        return self._draw(vectors.TWIN_STREAM, seed, serial)


# the request generators a traffic file of this kind may name
GENERATORS = {"held_out": _Stream}


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    name = traffic["generator"]
    if name not in GENERATORS:
        raise SystemExit(f"benchmark: deployment kind 'vectorsearch' has "
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
    window: the ledger's bytes by tenant."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    g = config["guarantees"]
    out = reference.hold(
        held, reference.Reference(built["corpus"]["vectors"],
                                  config["space_type"]),
        int(config["k"]), float(g["score_rtol"]),
        float(g["recall_at_k_floor"]))
    out["residency"] = {"hbm_ledger_bytes": {
        k: t["bytes"] for k, t in LEDGER.snapshot()["tenants"].items()}}
    return out


def counters(client) -> dict:
    return {f"{prefix}.{k}": v for prefix, group in _counter_groups().items()
            for k, v in group.items()}
