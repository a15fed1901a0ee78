"""The codec-v2 first pass reads its kept blocks one row of IMPACT_BLOCK
slots a block (ISSUE 39): a slot's block is its row index, nothing is
searched and no element is gathered alone. Held here on drawn plans: the
gather's valid (doc, impact, block) triples are, block after block and
each block turned back by its window's first lane, what a plain loop over
`bstart` / `blen` yields; `impact_score_blocks`' scores and counts are
`np.add.at` over the loop's triples in the loop's order, bit for bit; and
the lowered `impact_program` holds no `while` (the per-slot binary search
of the flat form, kept below as the reference, is one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.index.segment import IMPACT_BLOCK
from opensearch_tpu.ops import scoring as ops
from opensearch_tpu.search import compiler as C

NDOCS_PAD = 1 << 16


def _flat_search_gather(doc_ids, impacts, bstart, blen, bucket):
    """The gather as it stood: one flat bucket of slots, each finding its
    block by a binary search over the cumulative block lengths."""
    cum = jnp.cumsum(blen)
    i = jnp.arange(bucket, dtype=jnp.int32)
    b_idx = jnp.minimum(
        jnp.searchsorted(cum, i, side="right").astype(jnp.int32),
        bstart.shape[0] - 1)
    prev = jnp.where(b_idx > 0, cum[jnp.maximum(b_idx - 1, 0)], 0)
    src = jnp.clip(bstart[b_idx] + (i - prev), 0, doc_ids.shape[0] - 1)
    valid = i < cum[-1]
    return (jnp.where(valid, doc_ids[src], jnp.int32(2**31 - 1)),
            jnp.where(valid, impacts[src], 0), b_idx, valid)


def _planes(rng, P, bits, rows):
    """Posting planes padded as `segment._post_field_arrays` pads them
    (to a power of two: none at all when P is one). A kept row is what a
    posting row is: distinct documents in ascending order."""
    ppad = 1 << (P - 1).bit_length()
    docs = np.full(ppad, 2**31 - 1, np.int32)
    docs[:P] = rng.integers(0, NDOCS_PAD - 100, P)
    for first, n in rows:
        docs[first: first + n] = np.sort(rng.choice(
            NDOCS_PAD - 100, n, replace=False))
    q = np.zeros(ppad, np.uint8 if bits == 8 else np.uint16)
    q[:P] = rng.integers(1, 1 << bits, P)
    return docs, q


def _plan(rng, P, B_pad, rows):
    """`rows`: [(first posting, postings)] of the kept rows; each is cut
    into IMPACT_BLOCK-posting blocks, the last one partial, as
    `impactpath._plan_blocks` cuts them; the slots past them are empty."""
    offs, lens, w = [], [], []
    for first, n in rows:
        off = np.arange(first, first + n, IMPACT_BLOCK)
        offs.append(off)
        lens.append(np.minimum(IMPACT_BLOCK, first + n - off))
        w.append(np.full(len(off), rng.uniform(0.1, 4.0), np.float32))
    offs, lens, w = (np.concatenate(x) for x in (offs, lens, w))
    assert len(offs) <= B_pad and offs.max() + lens[-1] <= P
    bstart = np.zeros(B_pad, np.int32)
    blen = np.zeros(B_pad, np.int32)
    bweight = np.zeros(B_pad, np.float32)
    bstart[: len(offs)], blen[: len(offs)] = offs, lens
    bweight[: len(offs)] = w
    return bstart, blen, bweight


def _full_blocks(rng):
    return 5000, 8, [(256, 384), (1300, 512)]


def _row_end_partials(rng):
    return 5000, 16, [(7, 300), (1000, 129), (2000, 1), (3000, 127)]


def _empty_tail(rng):
    return 5000, 64, [(0, 200), (900, 130)]


def _one_block(rng):
    return 300, 8, [(40, 77)]


def _under_two_plane_rows(rng):
    # planes of 128 slots: shorter than the two rows a window is read from
    return 100, 8, [(10, 60), (75, 25)]


def _to_the_planes_end(rng):
    # P is a power of two, so the planes carry no padding, and the last
    # kept block is a partial one that ends with the plane
    return 2048, 8, [(100, 256), (2048 - 200, 200)]


def _wide(rng):
    rows, at = [], 0
    for _ in range(12):
        n = int(rng.integers(30000, 43000))
        rows.append((at, n))
        at += n + int(rng.integers(0, 500))
    return at, 4096, rows


CASES = {"full_blocks": _full_blocks, "row_end_partials": _row_end_partials,
         "empty_tail": _empty_tail, "one_block": _one_block,
         "to_the_planes_end": _to_the_planes_end,
         "under_two_plane_rows": _under_two_plane_rows, "wide_4096": _wide}


@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_slots_block_is_its_row(case, bits):
    rng = np.random.default_rng([len(case), bits])
    P, B_pad, rows = CASES[case](rng)
    docs, q = _planes(rng, P, bits, rows)
    bstart, blen, bweight = _plan(rng, P, B_pad, rows)

    # the plain loop: block after block, posting after posting
    want = [(int(docs[s + j]), int(q[s + j]), b)
            for b, (s, n) in enumerate(zip(bstart, blen)) for j in range(n)]

    g_docs, g_q, g_valid = jax.device_get(jax.jit(
        ops.gather_impact_blocks, static_argnums=4)(
            docs, q, bstart, blen, IMPACT_BLOCK))
    assert g_docs.shape == g_q.shape == g_valid.shape == (B_pad,
                                                          IMPACT_BLOCK)
    assert g_q.dtype == q.dtype         # still the quantized domain
    # a slot's block is its row; a row holds its window rotated by the
    # lane the window starts at in the planes' [P / 128, 128] view
    got = []
    for b in range(B_pad):
        turn = -(int(bstart[b]) % IMPACT_BLOCK)
        ok = np.roll(g_valid[b], turn)
        assert ok[: blen[b]].all() and not ok[blen[b]:].any()
        got += [(d, i, b) for d, i in zip(
            np.roll(g_docs[b], turn)[: blen[b]].tolist(),
            np.roll(g_q[b], turn)[: blen[b]].tolist())]
    assert got == want
    # what is not a posting can be dropped by the scatters
    assert (g_docs[~g_valid] == 2**31 - 1).all() and not g_q[~g_valid].any()

    # ... and they are the flat search form's triples, in its order
    f_docs, f_q, f_b, f_valid = jax.device_get(jax.jit(
        _flat_search_gather, static_argnums=4)(
            docs, q, bstart, blen, B_pad * IMPACT_BLOCK))
    assert want == list(zip(f_docs[f_valid].tolist(),
                            f_q[f_valid].tolist(), f_b[f_valid].tolist()))

    # the accumulated planes: np.add.at over the plain loop's triples, in
    # the loop's order (a block's postings are distinct documents, so the
    # rotation inside a block reorders no document's additions)
    live = np.ones(NDOCS_PAD, np.float32)
    live[::7] = 0.0
    sm = jax.device_get(jax.jit(
        ops.impact_score_blocks, static_argnums=(6, 7))(
            docs, q, live, bstart, blen, bweight, IMPACT_BLOCK, NDOCS_PAD))
    d = np.asarray([t[0] for t in want])
    contrib = (np.asarray([t[1] for t in want]).astype(np.float32)
               * bweight[[t[2] for t in want]])
    scores = np.zeros(NDOCS_PAD, np.float32)
    counts = np.zeros(NDOCS_PAD, np.float32)
    np.add.at(scores, d, contrib)
    np.add.at(counts, d, np.float32(1.0))
    np.testing.assert_array_equal(sm.scores, np.where(live > 0, scores, 0))
    np.testing.assert_array_equal(sm.count, np.where(live > 0, counts, 0))

    # the search cannot come back unnoticed: the program's lowering holds
    # no loop at all (the flat form's does: the detector sees one)
    prog = C.build_impact_program(B_pad, 32, bits)._fn
    text = prog.lower(docs, q, live, bstart, blen, bweight,
                      np.float32(1.0)).as_text()
    assert "while" not in text
    assert "while" in jax.jit(_flat_search_gather, static_argnums=4).lower(
        docs, q, bstart, blen, B_pad * IMPACT_BLOCK).as_text()
