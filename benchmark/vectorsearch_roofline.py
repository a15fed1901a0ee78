"""What a `knn` query has to read, and how long the device took over it.

`stage_bytes`: the bytes the configured method has to move from HBM for one
query, from the configuration's shapes and the build's read-out, whatever
the program then does. For `ivf`: the centroids and the mean fill of the
probed lists, `(nlist + nprobe * ndocs / nlist) * dims * 4`: a list's empty
slots, the `cap` a balanced list is padded to, a second pass over gathered
rows and the scatter back into the doc space are waste, not work. For an
exact scan (`flat`): every row once, `ndocs * dims * 4`. At batch 1 the
stage is bound by memory (2 flops a 4-byte element, against the chip's 240
flops a byte), so bytes over `peaks.json`'s `hbm_bytes_per_s` is the least
time it could take, and that over the device's time in the `knn.*` scopes
its share of the roofline (`knn_stage_hbm_roofline_share`).

`build_readout` / `scope_seconds`: what the three `knn_*` trace metrics and
`ivf_build_s` share. The build's counters (`ops.ann.IVF_STATS`) are read
from the program itself, not from the window's deltas: the build is over
before the window opens, so its delta reads 0. A program without the group
(the parent of the PR that added it) reads as None."""

from __future__ import annotations

import functools
import glob
import os

import span_reduce
import xplane_scopes

SCOPES = ("knn.centroids", "knn.gather", "knn.score", "knn.scatter",
          "knn.scan")


def stage_bytes(method: str, ndocs: int, dims: int, nlist: int = 0,
                nprobe: int = 0) -> float:
    if method == "ivf":
        return (nlist + nprobe * ndocs / nlist) * dims * 4.0
    if method == "flat":
        return ndocs * dims * 4.0
    raise ValueError(f"method {method!r} (has ivf, flat)")


def build_readout():
    """The program's IVF build counters as they stand, or None."""
    try:
        from opensearch_tpu.ops import ann
    except ImportError:
        return None
    group = getattr(ann, "IVF_STATS", None)
    return None if group is None else dict(group.items())


@functools.lru_cache(maxsize=1)
def _seconds_of(path: str, mtime: float) -> dict:
    """{scope: device seconds} of one trace file (three readers ask; the
    file is walked once a scope)."""
    try:
        return {scope: (xplane_scopes.scope_seconds(path, scope)
                        or (0.0, 0.0))[0] for scope in SCOPES}
    except (ValueError, IndexError, OSError):
        return {}


def scope_seconds(ctx):
    """{scope: device seconds in the traced slice} for the `knn.*` scopes,
    or None where the run has no trace or its ops name none of them."""
    found = glob.glob(os.path.join(span_reduce.OUT_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not found or not ctx.get("trace"):
        return None
    path = max(found, key=os.path.getmtime)
    out = _seconds_of(path, os.path.getmtime(path))
    return out if sum(out.values()) > 0 else None


def query_bytes(ctx):
    """`stage_bytes` of the window's own queries, from the build's
    read-out (rows, nlist) and the window's counters (the probe width and
    the padded vector a query brought); None where either is missing or
    the window probed no list."""
    built, c = build_readout(), ctx["window"]["counters"]
    n_ann, n = c.get("knn.ann_queries"), c.get("knn.queries")
    if not built or not built["nlist"] or not n_ann or not n:
        return None
    dims = c["knn.query_vector_bytes"] / n / 4.0
    return stage_bytes("ivf", built["rows"], dims, built["nlist"],
                       c["knn.lists_probed"] / n_ann)
