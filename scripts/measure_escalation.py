"""Measure the pruned-path escalation rate on the bench's own query
streams, CPU-only (no chip needed): load the cached 8.8M corpus, run the
config-1 two-term and config-1r realistic streams through the product
search path with the dense rerun SHORT-CIRCUITED, and report
served/escalated plus the bound-vs-theta gap distribution.

The escalation rate is THE number that decides config 1: an escalated
query pays the pruned pass AND the dense pass. Run:
`python scripts/measure_escalation.py [nqueries]`
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np

import bench as B
from opensearch_tpu.ops.pallas_bm25 import DL_BITS, DL_MASK, LANES
from opensearch_tpu.rest.client import RestClient
from opensearch_tpu.search import fastpath

TF_SHIFT_MASK = (1 << 11) - 1


def sim_vec(ndocs):
    """Vectorized numpy stand-in for the TPU kernel (same semantics as
    tests/test_pruned.sim_fused_bm25_topk_tfdl, but np.add.at over a dense
    per-doc accumulator so 8.8M-doc corpora are feasible on host)."""
    def fused(d_docs, d_tfdl, rowstarts, nrows, lens, skips, weights, msm,
              avgdl, dlo, dhi, T, L, K, k1, b):
        docs_a = np.asarray(d_docs).ravel()
        tfdl_a = np.asarray(d_tfdl).ravel()
        QB = rowstarts.shape[0]
        out_s = np.full((QB, 128), -np.inf, np.float32)
        out_d = np.full((QB, 128), -1, np.int32)
        out_t = np.zeros((QB, 128), np.int32)
        for q in range(QB):
            # compact per-row accumulation (a dense ndocs-sized array per
            # kernel row melts down on chunked dense reruns)
            wds, contribs = [], []
            for t in range(T):
                if nrows[q, t] == 0:
                    continue
                base = int(rowstarts[q, t]) * LANES + int(skips[q, t])
                ln = int(lens[q, t])
                w = np.float32(weights[q, t])
                wd = docs_a[base: base + ln]
                wp = tfdl_a[base: base + ln]
                sel = (wd >= dlo[q, 0]) & (wd < dhi[q, 0])
                wd = wd[sel]
                wp = wp[sel]
                tf = ((wp >> DL_BITS) & TF_SHIFT_MASK).astype(np.float32)
                dl = (wp & DL_MASK).astype(np.float32)
                k = k1 * (1.0 - b + b * dl / np.float32(avgdl[q, 0]))
                wds.append(wd)
                contribs.append((w * tf / (tf + k)).astype(np.float32))
            if not wds:
                continue
            allw = np.concatenate(wds)
            cand, inv = np.unique(allw, return_inverse=True)
            cs = np.zeros(len(cand), np.float32)
            cn = np.zeros(len(cand), np.int32)
            np.add.at(cs, inv, np.concatenate(contribs))
            np.add.at(cn, inv, 1)
            ok = cn >= msm[q, 0]
            cand, cs = cand[ok], cs[ok]
            out_t[q, :] = len(cand)
            order = np.lexsort((cand, -cs))[:K]
            out_s[q, : len(order)] = cs[order]
            out_d[q, : len(order)] = cand[order]
        return out_s, out_d, out_t
    return fused


def main():
    nq = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    ndocs = int(os.environ.get("BENCH_NDOCS", 8_800_000))
    t0 = time.time()
    starts, doc_ids, tfs, dl, df_per_term = B._cached(
        f"body_{ndocs}", lambda: B.build_corpus(ndocs), True)
    queries = B.pick_queries(df_per_term, nq)
    queries_real = B.pick_queries_real(df_per_term, nq)
    (tstarts, tdoc_ids, ttfs, tpos_starts, tpositions,
     pair_first, pair_second, pair_counts) = B._cached(
        f"title_{ndocs}", lambda: B.build_title_corpus(ndocs), True)
    rng = np.random.default_rng(3)
    status_ord = rng.integers(0, 3, ndocs).astype(np.int32)
    price = rng.integers(0, 1000, ndocs).astype(np.int64)
    vocab_strs = [f"t{i:07d}" for i in range(len(df_per_term))]
    tvocab_strs = [f"p{i:04d}" for i in range(len(tstarts) - 1)]
    client = RestClient()
    B.make_index(client, (starts, doc_ids, tfs, vocab_strs), dl,
                 (tstarts, tdoc_ids, ttfs, tpos_starts, tpositions,
                  tvocab_strs), status_ord, price)
    # stand the vectorized simulator in for the TPU kernel (same pattern
    # as tests/test_pruned.py) so the verify/escalate decision logic runs
    # with REAL bench-scale heads on host
    fastpath.fused_bm25_topk_tfdl = sim_vec(ndocs)
    fastpath._backend_ok = True
    print(f"setup {time.time()-t0:.1f}s", flush=True)

    gaps = []          # (bound - theta) / max(theta, eps) per verify call
    outcomes = {"serve": 0, "escalate": 0, "tie_serve": 0}
    orig_verify = fastpath._verify_pruned
    orig_tie = fastpath._tie_serves
    tie_hits = [0]

    def tie_spy(*a, **k):
        r = orig_tie(*a, **k)
        if r:
            tie_hits[0] += 1
        return r

    def spy(seg, vq, sc, dc, total, window, K):
        valid = np.isfinite(sc) & (dc >= 0)
        fastpath._tie_serves = tie_spy
        before_tie = tie_hits[0]
        r = orig_verify(seg, vq, sc, dc, total, window, K)
        fastpath._tie_serves = orig_tie
        # recompute the gap for reporting — MIRROR _verify_pruned's
        # partial_k rule (0 when the kernel window wasn't full)
        try:
            pb = seg.postings.get(vq.field)
            dlc = seg.doc_lens.get(vq.field)
            al = fastpath.get_aligned(seg, vq.field)
            cand = dc[valid]
            pk = float(sc[valid][-1]) if len(cand) == len(sc) else 0.0
            b = fastpath._unseen_bound(al, pb, dlc, vq, pk)
            gaps.append(float(b))
        except Exception:
            pass
        if r is None:
            outcomes["escalate"] += 1
            # real path continues: phase-2 union rescore, then dense sim
            return None
        outcomes["serve"] += 1
        if tie_hits[0] > before_tie:
            outcomes["tie_serve"] += 1
        return r

    fastpath._verify_pruned = spy

    streams = [("config1_2term", queries, lambda q: q[:2]),
               ("config1r_6term", queries_real, lambda q: q)]
    pick = os.environ.get("ESC_STREAMS")
    if pick:
        names = [s[0] for s in streams]
        wanted = pick.split(",")
        streams = [s for s in streams if s[0] in wanted]
        if not streams:
            raise SystemExit(f"ESC_STREAMS={pick!r} matches none of "
                             f"{names}")
    # each stream runs TWICE: phase-2 rescore on the host numpy oracle,
    # then on the device kernel (ops/rescore.py — real jnp program, here
    # on the CPU backend). The serve/dense split and the served pages must
    # be BIT-IDENTICAL between the two; what differs is where the rescore
    # wall time goes (RESCORE_STATS) — the number that decides whether the
    # escalation ladder still serializes on the host.
    modes = [m.strip().lower() for m in
             os.environ.get("ESC_RESCORE", "host,device").split(",")
             if m.strip()]
    bad = [m for m in modes if m not in ("host", "device")]
    if bad:
        raise SystemExit(f"ESC_RESCORE modes must be host/device, got {bad}")
    mismatches = 0
    for name, qs, terms_of in streams:
        per_mode = {}
        for mode in modes:
            fastpath.set_rescore_mode(mode)
            outcomes.update({"serve": 0, "escalate": 0, "tie_serve": 0})
            gaps.clear()
            before = dict(fastpath.STATS)
            before_r = dict(fastpath.RESCORE_STATS)
            t0 = time.time()
            lines = []
            for i in range(len(qs)):
                lines.append({"index": "bench"})
                lines.append({"query": {"match": {"body": " ".join(
                    vocab_strs[t] for t in terms_of(qs[i]))}},
                    "size": 10, "_bench": f"esc-{name}-{mode}-{i}"})
            resp = client.msearch(lines)
            ds = {k: fastpath.STATS[k] - before[k] for k in fastpath.STATS
                  if fastpath.STATS[k] != before[k]}
            dr = {k: round(fastpath.RESCORE_STATS[k] - before_r[k], 2)
                  for k in fastpath.RESCORE_STATS
                  if fastpath.RESCORE_STATS[k] != before_r[k]}
            # served-page digest: hit ids + exact score bytes per query
            digest = [tuple((h["_id"], h["_score"])
                            for h in r["hits"]["hits"])
                      for r in resp["responses"]]
            tot = outcomes["serve"] + outcomes["escalate"]
            print(f"{name}[rescore={mode}]: n={len(qs)} verify_calls={tot} "
                  f"serve={outcomes['serve']} "
                  f"(ties {outcomes['tie_serve']}) "
                  f"escalate={outcomes['escalate']} "
                  f"rate={outcomes['escalate']/max(tot,1):.1%} "
                  f"stats={ds} rescore={dr} "
                  f"wall={time.time()-t0:.1f}s", flush=True)
            per_mode[mode] = (ds, digest)
        fastpath.set_rescore_mode(None)
        if {"host", "device"} <= set(per_mode):
            ds_h, dig_h = per_mode["host"]
            ds_d, dig_d = per_mode["device"]
            split_keys = ("pruned_served", "pruned_rescued",
                          "pruned_rescued2", "pruned_dview",
                          "pruned_escalated")
            split_h = {k: ds_h.get(k, 0) for k in split_keys}
            split_d = {k: ds_d.get(k, 0) for k in split_keys}
            same = split_h == split_d and dig_h == dig_d
            mismatches += 0 if same else 1
            print(f"{name}: host/device serve-dense split "
                  f"{'IDENTICAL' if same else 'MISMATCH'} "
                  f"host={split_h} device={split_d} "
                  f"pages_equal={dig_h == dig_d}", flush=True)
    if mismatches:
        raise SystemExit(f"{mismatches} stream(s) diverged between host "
                         f"and device rescore")


if __name__ == "__main__":
    main()
