"""The control of the kind `pmc`'s check: the reference itself, twice
weakened, in the program's place.

(a) `conjunction`: a phrase answered as the documents that hold every one
of its words, the frequency the least term frequency: what an index
without positions can say. It matches documents in which the words never
meet, so it fails by `total_violations`. (b) `presence`: the phrase
frequency capped at 1, presence alone (`match_only_text`'s answer): the
totals hold and every document that holds the phrase twice scores low, so
it fails by `score_rel_err_max`. Held to the exact reference by the kind's
own rule each has to come out not correct; a check that lets them pass
would let a program pass that reads no position or counts none. Host numpy
only: it touches no device (`benchmark/tests/test_pmc.py` keeps it at a
small size over two corpus seeds; PERF.md section 2 has the readings at
the cell's).

    python3 benchmark/pmc_control.py [ndocs] [requests]
"""

from __future__ import annotations

import pmc_reference as reference

CONTROLS = {"conjunction": "total_violations",
            "presence": "score_rel_err_max"}


def run(articles: dict, specs: list, rtol: float, exact=None) -> dict:
    """control name -> `specs` answered by that weakened reference and held
    to the exact one."""
    tok, offsets, live = articles["tok"], articles["offsets"], articles["live"]
    exact = exact or reference.Reference(tok, offsets, live)
    out = {}
    for how in CONTROLS:
        low = reference.Reference(tok, offsets, live, how=how)
        low.learn([tuple(s["terms"]) for s in specs])
        held = [(s, reference.as_response(low.page(s["terms"])))
                for s in specs]
        out[how] = reference.hold(held, exact, rtol)
    return out


if __name__ == "__main__":
    import json
    import sys

    import pmc_articles as articles
    import run as harness
    loaded = harness.load_cell("pmc.search1.phrase")
    config, traffic = loaded["config"], loaded["traffic"]
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else int(config["ndocs"])
    arts = articles.generate(ndocs, int(config["corpus_seed"]),
                             config["generator"])
    stream = harness.load_kind(config["deployment_kind"]).stream(
        {"articles": arts}, traffic, int(traffic["pool_seed"]))
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    out = run(arts, stream.take(n), float(config["guarantees"]["score_rtol"]))
    print(json.dumps({how: dict(r["numbers"], correct=r["correct"])
                      for how, r in out.items()} | {"ndocs": ndocs}))
