"""What the chip's compiler and the real kernels say, without a chip.

Two families, both in this ONE file (only one process at a time may load
the TPU's library; a second file could land on another xdist worker and
skip in silence):

* the three live Pallas kernels, `exact_rescore_batch`, the dense form
  of `ops.aggs`' per-bucket reductions and the phrase join COMPILED for a
  described (not attached) `v5e:2x2` device at real buckets — what the
  compiler refuses here costs no chip time. The topology, the sharding
  and the shapes are built inside module-scoped fixtures that skip when
  the topology cannot be described: nothing at import, no child process,
  the persistent compilation cache off around them (such a compile can be
  written to it but not read back without a chip).
* the REAL tfdl, bool and impact kernels under
  `pltpu.force_tpu_interpret_mode()` at tiny shapes against the numpy
  simulators that stand in for them in tests/test_pruned.py — so the
  stand-ins the rest of tier-1 trusts are themselves checked.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from opensearch_tpu.ops.pallas_bm25 import (DL_BITS, HBM_ALIGN, INT_SENTINEL,
                                            LANES, REQ_W,
                                            fused_bm25_bool_topk,
                                            fused_bm25_topk_impact,
                                            fused_bm25_topk_tfdl)
from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.ops.rescore import exact_rescore_batch, plane_in_vmem
from tests.test_pruned import (sim_fused_bm25_topk_impact,
                               sim_fused_bm25_topk_tfdl)

K1, B = 1.2, 0.75
P_REAL = 1 << 27        # a 2.2M-doc MS-MARCO-shaped shard's aligned plane
P_SMALL = 24_359_552    # a 171k-doc TREC-COVID-shaped collection's


# ---------------------------------------------------------------------
# compiled for a described v5e
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """(shape, dtype) -> ShapeDtypeStruct placed on the first described
    chip, with the persistent compilation cache off while the module's
    compile tests run."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _per_query(S, QB, T, with_avgdl=True):
    """rowstarts, nrows, lens, skips, weights, msm[, avgdl], dlo, dhi."""
    i32, f32 = jnp.int32, jnp.float32
    out = [S((QB, T), i32)] * 4 + [S((QB, T), f32), S((QB, 1), f32)]
    if with_avgdl:
        out.append(S((QB, 1), f32))
    return out + [S((QB, 1), i32), S((QB, 1), i32)]


def _assert_mosaic(compiled, name):
    """A Mosaic custom call whose HLO instruction carries the kernel's
    pinned `name=`: `benchmark/trace_reduce.py` finds the kernels on the
    device trace by the `%fused_bm25_` prefix of that instruction."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert name.startswith("fused_bm25_")
    assert re.search(rf"%{name}[.\d]* = .* custom-call\(", text)


@pytest.mark.parametrize("QB,T,L,K", [(32, 2, 4096, 16), (8, 4, 4096, 16)])
def test_tfdl_kernel_compiles_for_v5e(shape_on_chip, QB, T, L, K):
    S = shape_on_chip
    planes = [S((P_REAL,), jnp.int32)] * 2
    _assert_mosaic(fused_bm25_topk_tfdl.lower(
        *planes, *_per_query(S, QB, T), T=T, L=L, K=K, k1=K1, b=B
    ).compile(), "fused_bm25_topk_tfdl")


@pytest.mark.parametrize("QB,T,L,K", [(32, 2, 8192, 128), (8, 4, 4096, 16)])
def test_impact_kernel_compiles_for_v5e(shape_on_chip, QB, T, L, K):
    S = shape_on_chip
    planes = [S((P_REAL,), jnp.int32)] * 2
    _assert_mosaic(fused_bm25_topk_impact.lower(
        *planes, *_per_query(S, QB, T, with_avgdl=False), T=T, L=L, K=K
    ).compile(), "fused_bm25_topk_impact")


@pytest.mark.parametrize("QB,TS,L,K,filtered", [(8, 4, 4096, 16, False),
                                                (8, 2, 4096, 16, True)])
def test_bool_kernel_compiles_for_v5e(shape_on_chip, QB, TS, L, K, filtered):
    S = shape_on_chip
    i32, f32 = jnp.int32, jnp.float32
    T = 2 * TS if filtered else TS
    _assert_mosaic(fused_bm25_bool_topk.lower(
        S((P_REAL,), i32), S((P_REAL,), i32), S((1 << 22,), i32),
        *[S((QB, T), i32)] * 4, S((QB, TS), f32), S((QB, T), f32),
        S((QB, 1), f32), S((QB, 1), f32), S((QB, 1), i32), S((QB, 1), i32),
        TS=TS, L=L, K=K, k1=K1, b=B, filtered=filtered).compile(),
        "fused_bm25_bool_topk")


@pytest.mark.parametrize("P,QB,T,C", [(P_REAL, 8, 2, 256),
                                      (P_REAL, 64, 4, 2048),
                                      (P_SMALL, 1, 8, 32768)])
def test_exact_rescore_compiles_for_v5e(shape_on_chip, P, QB, T, C):
    S = shape_on_chip
    i32, f32 = jnp.int32, jnp.float32
    compiled = exact_rescore_batch.lower(
        S((P,), i32), S((P,), i32), S((QB, T), i32),
        S((QB, T), i32), S((QB, T), f32), S((QB, 1), f32), S((QB, C), i32),
        S((T,), i32), T=T, C=C, k1=K1, b=B).compile()
    text = compiled.as_text()
    if plane_in_vmem(P):
        # the reason for the unrolled form: XLA prefetches the plane into
        # VMEM (memory space 1) for every probe round
        assert "while(" not in text
        assert len(re.findall(rf"s32\[{P}\]\{{[^}}]*S\(1\)\}}", text)) \
            >= int(P).bit_length()
    else:
        # the probe depth is an operand: one `while` a term slot, not
        # bit_length(P) unrolled gather rounds
        assert len(re.findall(r"\bwhile\(", text)) == T
    # the [QB, T, C] probe intermediates fit the chip beside the planes
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


N_TRIPS = 1 << 25       # the trip-analytics cell's padded rows
# a plane relaid as [blocks, rows] (a copy of it) and not viewed in tiles
RELAID = f"[{N_TRIPS >> 15},{1 << 15}]"


@pytest.mark.parametrize("nb,sumsq", [(101, False), (366, True),
                                      (agg_ops._DENSE_BUCKETS - 1, False)])
def test_dense_sub_metric_compiles_for_v5e(shape_on_chip, nb, sumsq):
    """`bucketed_sub_metric` under `_DENSE_BUCKETS` buckets at the cell's
    size: one loop over the blocks, no scatter, the planes viewed and not
    relaid, and no [rows, buckets] one-hot written (33.5M x 128 x 4 bytes
    would be 17 GB), nor the limbs' planes: the one temporary of the rows'
    size is the held ids."""
    S = shape_on_chip
    rows = (N_TRIPS,)
    compiled = jax.jit(
        lambda b, v, w, inv: agg_ops.bucketed_sub_metric(b, v, w, nb, inv,
                                                         sumsq)
    ).lower(S(rows, jnp.int32), S(rows, jnp.float32), S(rows, jnp.float32),
            S((), jnp.float32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 1
    assert "scatter(" not in text and RELAID not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 160 << 20


def _counts_compiled(S, rows, nb, spanned):
    """`bucket_counts` over `rows` rows into `nb` buckets compiled for the
    chip, without a row span (a loop of static length) or with one (two
    traced scalars bound it: what a launch that carries aggregations
    runs since PR 49)."""
    if not spanned:
        return jax.jit(lambda b, w: agg_ops.bucket_counts(b, w, nb)).lower(
            S((rows,), jnp.int32), S((rows,), jnp.float32)).compile()
    return jax.jit(
        lambda b, w, lo, hi: agg_ops.bucket_counts(b, w, nb, (lo, hi))
    ).lower(S((rows,), jnp.int32), S((rows,), jnp.float32),
            S((), jnp.int32), S((), jnp.int32)).compile()


@pytest.mark.parametrize("spanned", [False, True])
@pytest.mark.parametrize("nb", [101, 256, 366])
def test_dense_bucket_counts_compile_for_v5e(shape_on_chip, nb, spanned):
    compiled = _counts_compiled(shape_on_chip, N_TRIPS, nb, spanned)
    text = compiled.as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 1
    assert "scatter(" not in text and RELAID not in text
    # the held ids, once: nothing of rows x buckets
    assert compiled.memory_analysis().temp_size_in_bytes < 160 << 20


N_EVENTS = 1 << 24      # the big5 cell's padded rows


@pytest.mark.parametrize("spanned", [False, True])
@pytest.mark.parametrize("nb", [agg_ops._DENSE_BUCKETS, 16_384, 65_536,
                                agg_ops._PRODUCT_BUCKETS - 1])
def test_product_bucket_counts_compile_for_v5e(shape_on_chip, nb, spanned):
    """`bucket_counts` between the constants at the big5 cell's size: one
    loop over the blocks, no scatter, one convolution whose operands are
    the comparisons themselves (neither one-hot is written: 2^24 x 512 x 2
    bytes would be 17 GB), the plane viewed and not relaid; no temporary
    but the held ids (the scatter's own)."""
    assert agg_ops.count_form(nb) == "product"
    compiled = _counts_compiled(shape_on_chip, N_EVENTS, nb, spanned)
    text = compiled.as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 1
    assert "scatter(" not in text
    assert len(re.findall(r" convolution\(", text)) == 1
    # no one-hot leaves its fusion, and the plane is not relaid (a copy of
    # it would be 64 MiB more)
    assert not re.search(r"bf16\[\d+,%d\]" % agg_ops._PRODUCT_BLOCK, text)
    assert compiled.memory_analysis().temp_size_in_bytes < 80 << 20


POSITION_SLOTS = 5 << 27    # a PMC shard's positional planes (667M positions)


@pytest.mark.parametrize("m,bucket,levels", [(2, 1 << 16, 3),
                                             (3, 1 << 20, 4),
                                             (2, 1 << 22, 4)])
def test_phrase_join_compiles_for_v5e(shape_on_chip, m, bucket, levels):
    """The phrase join (slop 0) over resident planes and their fence levels
    at the `pmc` shard's size: the anchor's window and the searches' top
    level are dynamic slices (not gathers of `bucket` slots), a probe below
    the top gathers a row of 128 a plane and not an element, nothing a
    plane long is written, the only scatter is the one into the document
    plane, a gathered block keeps its layout (no copy of it before the
    compare), and the gathered rows of a pass (2^16 anchors: 32 MiB a
    plane) bound the temporaries whatever the bucket."""
    from opensearch_tpu.ops import positions as pos_ops
    S = shape_on_chip
    ndocs_pad = 1 << 17
    assert pos_ops.search_levels(POSITION_SLOTS) == 5

    def join(planes, off, n, shift, dl, live, w, avgdl):
        wins = [pos_ops.resident(planes, off[i], n[i], levels)
                for i in range(m)]
        ad, ap = pos_ops.anchor_window(wins[0], bucket)
        freq = pos_ops.phrase_freqs(
            ad, ap, wins[1:], jnp.float32(0), ndocs_pad,
            shifts=[shift[i] for i in range(1, m)])
        return pos_ops.phrase_score(freq, dl, live, w, K1, B, avgdl)
    i32, f32 = jnp.int32, jnp.float32
    planes = {}
    for k in range(levels):     # a level's entries, in whole rows
        rows = -(-POSITION_SLOTS // pos_ops.ROW ** (k + 1))
        for plane in ("doc", "pos"):
            planes[pos_ops.plane_key(plane, k)] = S((rows * pos_ops.ROW,), i32)
    compiled = jax.jit(join).lower(
        planes, S((m,), i32), S((m,), i32), S((m,), i32),
        S((ndocs_pad,), f32), S((ndocs_pad,), f32), S((), f32),
        S((), f32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r" dynamic-slice\(", text)) >= 4
    assert len(re.findall(r" scatter\(", text)) == 1
    assert not re.search(r"s32\[%d\]\{0\} (copy|fusion)\(" % POSITION_SLOTS,
                         text)
    # the searches' gathers fetch rows: (levels - 1) a plane and further
    # term; the element gathers left are the landing slot's and its left
    # neighbour's (`nearest_delta`)
    per_pass = min(bucket, pos_ops.PASS)
    rows = re.findall(r"s32\[%d,%d\]\S* gather\(" % (per_pass, pos_ops.ROW),
                      text)
    assert len(rows) == 2 * (levels - 1) * (m - 1), len(rows)
    assert not re.search(r"s32\[%d,%d\]\S* copy\(" % (per_pass, pos_ops.ROW),
                         text)
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20


# ---------------------------------------------------------------------
# the real kernels, interpreted, against their numpy stand-ins
# ---------------------------------------------------------------------

NDOCS, T, L, K, QB = 3000, 2, 2 * HBM_ALIGN, 16, 4


@pytest.fixture(scope="module")
def tiny():
    """Three doc-ascending posting rows (one longer than an HBM tile) in
    the aligned layout the fastpath builds, plus per-query windows that
    start mid-tile (non-zero `skips`) and one absent term."""
    rng = np.random.default_rng(5)
    row_lens = [1500, 700, 40]
    starts, docs, tfdl, imp = [], [], [], []
    at = 0
    for n in row_lens:
        d = np.sort(rng.choice(NDOCS, n, replace=False)).astype(np.int32)
        tf = rng.integers(1, 6, n)
        dl = rng.integers(8, 120, n)
        pad = -(-n // LANES) * LANES - n
        starts.append(at)
        docs += [d, np.full(pad, INT_SENTINEL, np.int32)]
        tfdl += [((tf << DL_BITS) | dl).astype(np.int32),
                 np.zeros(pad, np.int32)]
        imp += [rng.integers(1, 256, n).astype(np.int32),
                np.zeros(pad, np.int32)]
        at += n + pad
    margin = [np.full(2 * L, INT_SENTINEL, np.int32)]
    docs = np.concatenate(docs + margin)
    tfdl = np.concatenate(tfdl + [np.zeros(2 * L, np.int32)])
    imp = np.concatenate(imp + [np.zeros(2 * L, np.int32)])

    # queries: (row0,row1) (row1,row2) (row2,absent) (row0,row2)
    pairs = [(0, 1), (1, 2), (2, -1), (0, 2)]
    rowstarts = np.zeros((QB, T), np.int32)
    nrows = np.zeros((QB, T), np.int32)
    lens = np.zeros((QB, T), np.int32)
    skips = np.zeros((QB, T), np.int32)
    for q, rows in enumerate(pairs):
        for t, r in enumerate(rows):
            if r < 0:
                continue
            dma = (starts[r] // HBM_ALIGN) * HBM_ALIGN
            skip = starts[r] - dma
            need = -(-(skip + row_lens[r]) // LANES)
            nr = HBM_ALIGN // LANES
            while nr < need:
                nr *= 2
            rowstarts[q, t], nrows[q, t] = dma // LANES, nr
            lens[q, t], skips[q, t] = row_lens[r], skip
    assert skips.max() > 0      # the mid-tile window is exercised
    weights = rng.uniform(0.5, 3.0, (QB, T)).astype(np.float32)
    return dict(docs=docs, tfdl=tfdl, imp=imp, rowstarts=rowstarts,
                nrows=nrows, lens=lens, skips=skips, weights=weights,
                msm=np.array([[1], [2], [1], [1]], np.float32),
                avgdl=np.full((QB, 1), 50.0, np.float32),
                dlo=np.zeros((QB, 1), np.int32),
                dhi=np.array([[NDOCS], [NDOCS], [NDOCS], [2000]], np.int32))


def _assert_same(got, want):
    """Kernel output vs simulator output: totals and doc ids exact, scores
    to f32 rounding (the simulator accumulates in f64)."""
    gs, gd, gt = (np.asarray(a) for a in got)
    ws, wd, wt = want
    np.testing.assert_array_equal(gt[:, 0], wt[:, 0])
    np.testing.assert_array_equal(gd[:, :K], wd[:, :K])
    hit = wd[:, :K] >= 0
    np.testing.assert_allclose(gs[:, :K][hit], ws[:, :K][hit], rtol=1e-6)
    assert np.all(np.isneginf(gs[:, :K][~hit]))


def test_tfdl_kernel_matches_its_simulator(tiny):
    q = tiny
    args = (q["docs"], q["tfdl"], q["rowstarts"], q["nrows"], q["lens"],
            q["skips"], q["weights"], q["msm"], q["avgdl"], q["dlo"],
            q["dhi"])
    with pltpu.force_tpu_interpret_mode():
        got = fused_bm25_topk_tfdl(*args, T=T, L=L, K=K, k1=K1, b=B)
    _assert_same(got, sim_fused_bm25_topk_tfdl(*args, T, L, K, K1, B))


def test_impact_kernel_matches_its_simulator(tiny):
    q = tiny
    args = (q["docs"], q["imp"], q["rowstarts"], q["nrows"], q["lens"],
            q["skips"], q["weights"], q["msm"], q["dlo"], q["dhi"])
    with pltpu.force_tpu_interpret_mode():
        got = fused_bm25_topk_impact(*args, T=T, L=L, K=K)
    _assert_same(got, sim_fused_bm25_topk_impact(*args, T, L, K))


@pytest.mark.parametrize("filtered", [False, True])
def test_bool_kernel_matches_the_tfdl_simulator(tiny, filtered):
    """The bool kernel has no stand-in of its own in tier-1; with every
    slot optional (cw 1, thresh = msm) it must answer exactly what the
    tfdl simulator does — and, filtered, what that simulator answers once
    docs outside the filter list are dropped (every slot required)."""
    q = tiny
    rng = np.random.default_rng(6)
    pad = np.zeros((QB, T), np.int32)
    if filtered:
        # AND of both terms AND the filter: cw REQ_W each, thresh 3*REQ_W
        keep = np.sort(rng.choice(NDOCS, 1800, replace=False)
                       ).astype(np.int32)
        filt = np.concatenate([keep, np.full(
            2 * L - len(keep) % (2 * L) + L, INT_SENTINEL, np.int32)])
        slot = lambda a, v: np.concatenate(                 # noqa: E731
            [a, np.full((QB, 1), v, a.dtype), pad[:, : T - 1]], axis=1)
        rowstarts, skips = slot(q["rowstarts"], 0), slot(q["skips"], 0)
        nrows = slot(q["nrows"], L // LANES)
        lens = slot(q["lens"], len(keep))
        cw = np.where(lens > 0, np.float32(REQ_W), np.float32(0.0))
        thresh = np.full((QB, 1), 3 * REQ_W, np.float32)
        msm = np.full((QB, 1), 2.0, np.float32)
    else:
        filt = np.full(LANES, INT_SENTINEL, np.int32)
        rowstarts, nrows, lens, skips = (q["rowstarts"], q["nrows"],
                                         q["lens"], q["skips"])
        cw = (lens > 0).astype(np.float32)
        thresh = msm = q["msm"]
    with pltpu.force_tpu_interpret_mode():
        got = fused_bm25_bool_topk(
            q["docs"], q["tfdl"], filt, rowstarts, nrows, lens, skips,
            q["weights"], cw, thresh, q["avgdl"], q["dlo"], q["dhi"],
            TS=T, L=L, K=K, k1=K1, b=B, filtered=filtered)
    docs = q["docs"]
    if filtered:
        # the reference sees only postings whose doc is in the filter
        docs = np.where(np.isin(docs, keep), docs, INT_SENTINEL)
        docs = np.where(docs == INT_SENTINEL, np.int32(-1), docs)
    want = sim_fused_bm25_topk_tfdl(
        docs, q["tfdl"], q["rowstarts"], q["nrows"], q["lens"], q["skips"],
        q["weights"], msm, q["avgdl"], q["dlo"], q["dhi"], T, L, K, K1, B)
    _assert_same(got, want)
