"""`Segment.live_count` is state that is kept, not a reduction that is run.

The invariant: whatever sequence of writes a segment has seen, `live_count`
equals the number of set rows of `live`, on every segment and every nested
child segment, and `_count` / `hits.total` of a `match_all` agree with it.
The mechanism: a delete and a read recount nothing, a whole-mask assignment
recounts once, and the array `live` hands out cannot be written through."""

import random

import numpy as np
import pytest

from opensearch_tpu.cluster.replication import ReplicaShard, promote_to_primary
from opensearch_tpu.index import reorder as R
from opensearch_tpu.index.segment import SEGMENT_STATS, Segment
from opensearch_tpu.rest.client import ApiError, RestClient

INDEX = "lc"
MAPPING = {"settings": {"number_of_shards": 1},
           "mappings": {"properties": {
               "title": {"type": "text"},
               "n": {"type": "long"},
               "comments": {"type": "nested", "properties": {
                   "author": {"type": "keyword"},
                   "stars": {"type": "integer"}}}}}}

# operation -> weight; every mix keeps every operation reachable
MIXES = {
    "balanced": {"index": 8, "delete": 4, "delete_twice": 2, "update": 3,
                 "refresh": 3, "forcemerge": 1, "reorder": 1, "save_load": 1,
                 "replica": 1, "reopen": 1},
    "delete_heavy": {"index": 6, "delete": 8, "delete_twice": 4, "update": 1,
                     "refresh": 3, "forcemerge": 1, "reorder": 1,
                     "save_load": 1, "replica": 1, "reopen": 1},
    "update_heavy": {"index": 4, "delete": 1, "delete_twice": 1, "update": 9,
                     "refresh": 3, "forcemerge": 1, "reorder": 1,
                     "save_load": 1, "replica": 1, "reopen": 1},
    "merge_heavy": {"index": 8, "delete": 4, "delete_twice": 1, "update": 3,
                    "refresh": 5, "forcemerge": 4, "reorder": 2,
                    "save_load": 1, "replica": 1, "reopen": 1},
    "restart_heavy": {"index": 8, "delete": 4, "delete_twice": 1, "update": 3,
                      "refresh": 2, "forcemerge": 1, "reorder": 1,
                      "save_load": 3, "replica": 3, "reopen": 4},
}
SEEDS = [11, 2147483659, 3000000019]
STEPS = 60


def _every_segment(segments):
    """The segments and, under them, every nested child segment."""
    for seg in segments:
        yield seg
        yield from _every_segment(b.child for b in seg.nested.values())


def _hold(segments):
    for seg in _every_segment(segments):
        assert seg.live_count == int(np.count_nonzero(seg.live)), seg.name
        assert type(seg.live_count) is int
        assert not seg.live.flags.writeable


def _doc(rng):
    return {"title": f"post {rng.randrange(50)}", "n": rng.randrange(1000),
            "comments": [{"author": rng.choice("abc"),
                          "stars": rng.randrange(6)}
                         for _ in range(rng.randrange(4))]}


class _Driver:
    """Random writes through `RestClient`, a model of the live ids beside
    them, and the whole-mask paths (`reorder`, `save` + `load`, a replica's
    copy, a restart with translog replay) on the engine's own segments."""

    def __init__(self, seed, mix, tmp_path):
        self.rng = random.Random(seed)
        self.ops, self.weights = zip(*sorted(MIXES[mix].items()))
        self.tmp_path = tmp_path
        self.data_path = str(tmp_path / "data")
        self.client = RestClient(data_path=self.data_path)
        self.client.indices.create(INDEX, MAPPING)
        self.live_ids = set()
        self.next_id = 0
        self.saves = 0

    @property
    def engine(self):
        return self.client.node.indices[INDEX].shards[0]

    def step(self):
        getattr(self, "op_" + self.rng.choices(self.ops, self.weights)[0])()
        _hold(self.engine.segments)

    def op_index(self):
        for _ in range(self.rng.randrange(1, 6)):
            doc_id = str(self.next_id)
            self.next_id += 1
            self.client.index(INDEX, _doc(self.rng), id=doc_id)
            self.live_ids.add(doc_id)

    def _delete(self, doc_id):
        try:
            return self.client.delete(INDEX, doc_id)["result"]
        except ApiError as e:
            assert e.status == 404
            return "not_found"

    def op_delete(self):
        if self.live_ids:
            doc_id = self.rng.choice(sorted(self.live_ids))
            assert self._delete(doc_id) == "deleted"
            self.live_ids.discard(doc_id)

    def op_delete_twice(self):
        if self.live_ids:
            doc_id = self.rng.choice(sorted(self.live_ids))
            assert self._delete(doc_id) == "deleted"
            assert self._delete(doc_id) == "not_found"
            self.live_ids.discard(doc_id)

    def op_update(self):
        if self.live_ids:
            doc_id = self.rng.choice(sorted(self.live_ids))
            self.client.update(INDEX, doc_id,
                               {"doc": {"n": self.rng.randrange(1000)}})

    def op_refresh(self):
        self.client.indices.refresh(INDEX)
        self.totals_agree()

    def op_forcemerge(self):
        self.client.indices.refresh(INDEX)
        self.client.indices.forcemerge(INDEX, max_num_segments=1)
        assert len(self.engine.segments) <= 1
        self.totals_agree()

    def op_reorder(self):
        for seg in self.engine.segments:
            perm = self.rng.sample(range(seg.ndocs), seg.ndocs)
            out = R.apply_permutation(seg, np.asarray(perm, np.int64))
            _hold([out])
            assert out.live_count == seg.live_count

    def op_save_load(self):
        for seg in self.engine.segments:
            self.saves += 1
            path = str(self.tmp_path / f"saved{self.saves}")
            seg.save(path)
            back = Segment.load(path)
            _hold([back])
            assert back.live_count == seg.live_count
            assert [b.live_count for b in _every_segment([back])] == \
                [s.live_count for s in _every_segment([seg])]

    def op_replica(self):
        self.client.indices.refresh(INDEX)
        rep = ReplicaShard(self.engine, 0, 0)
        rep.sync(warm=False)
        _hold(rep.segments)
        assert rep.num_docs == len(self.live_ids)
        promoted = promote_to_primary(self.engine.mappings, rep,
                                      self.engine.primary_term + 1)
        assert promoted.num_docs == len(self.live_ids)

    def op_reopen(self):
        # half the restarts find a commit and a translog tail, half a
        # translog alone beside an older commit: both replay deletes
        if self.rng.random() < 0.5:
            self.client.indices.flush(INDEX)
            self.op_index()
            self.op_delete()
        self.client.node.indices[INDEX].close()
        self.client = RestClient(data_path=self.data_path)
        self.totals_agree()

    def totals_agree(self):
        """After a refresh the mask's sum is what every reader reports."""
        self.client.indices.refresh(INDEX)
        _hold(self.engine.segments)
        want = len(self.live_ids)
        assert sum(s.live_count for s in self.engine.segments) == want
        assert self.client.count(INDEX)["count"] == want
        total = self.client.search(INDEX, {
            "query": {"match_all": {}}, "size": 0,
            "track_total_hits": True})["hits"]["total"]
        assert total == {"value": want, "relation": "eq"}
        cat = self.client.cat.segments(INDEX)
        assert sum(int(row["docs.count"]) for row in cat) == want


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("seed", SEEDS)
def test_live_count_is_the_masks_sum(seed, mix, tmp_path):
    d = _Driver(seed, mix, tmp_path)
    for _ in range(STEPS):
        d.step()
    d.totals_agree()
    d.op_forcemerge()
    d.op_reopen()


# ---------------- the mechanism ----------------

@pytest.fixture
def served():
    c = RestClient()
    c.indices.create(INDEX, MAPPING)
    rng = random.Random(5)
    for i in range(40):
        c.index(INDEX, _doc(rng), id=str(i))
    c.indices.refresh(INDEX)
    return c


def _segment(client):
    (seg,) = client.node.indices[INDEX].shards[0].segments
    return seg


def test_searches_of_an_unchanged_index_recount_nothing(served):
    before = SEGMENT_STATS["live_recounts"]
    for i in range(200):
        r = served.search(INDEX, {"query": {"range": {"n": {"gte": i}}},
                                  "size": 3})
        assert r["hits"]["total"]["relation"] == "eq"
    assert served.count(INDEX)["count"] == 40
    assert SEGMENT_STATS["live_recounts"] == before


def test_a_delete_steps_the_count_and_recounts_nothing(served):
    seg = _segment(served)
    before = SEGMENT_STATS["live_recounts"]
    gen = seg.live_gen
    assert seg.live_count == 40
    served.delete(INDEX, "7")
    assert seg.live_count == 39
    assert seg.live_gen == gen + 1
    assert SEGMENT_STATS["live_recounts"] == before


def test_a_second_delete_of_one_row_counts_once(served):
    seg = _segment(served)
    seg.delete_doc(3)
    gen = seg.live_gen
    seg.delete_doc(3)
    assert seg.live_count == 39 == int(np.count_nonzero(seg.live))
    # the second delete still bumps the generation, as it did before the
    # count was kept
    assert seg.live_gen == gen + 1


@pytest.mark.parametrize("mask", [
    np.ones(40, dtype=bool),                       # the benchmark builders' form
    np.arange(40) % 3 == 0,
    np.zeros(40, dtype=bool),
    (np.arange(40) % 2).astype(np.float32),       # not bool: taken as truth
], ids=["ones", "a_third", "zeros", "float32"])
def test_a_whole_mask_assignment_recounts_exactly_once(served, mask):
    seg = _segment(served)
    seg.delete_doc(0)
    gen = seg.live_gen
    before = SEGMENT_STATS["live_recounts"]
    seg.live = mask
    assert seg.live_count == int(np.count_nonzero(mask))
    assert SEGMENT_STATS["live_recounts"] == before + 1
    for _ in range(5):
        assert seg.live_count == int(np.count_nonzero(mask))
    assert SEGMENT_STATS["live_recounts"] == before + 1
    assert seg.live.dtype == bool
    assert seg.live_gen == gen      # assignment leaves the generation alone
    _hold([seg])


def test_an_assigned_read_only_mask_can_still_be_deleted_from(served):
    """merge.py saves `child.live` (the read-only view), assigns a
    temporary mask and assigns the saved one back: the segment must come
    out of that with a mask `delete_doc` can write."""
    seg = _segment(served)
    saved = seg.live
    seg.live = np.zeros(40, dtype=bool)
    seg.live = saved
    assert seg.live_count == 40
    seg.delete_doc(9)
    assert seg.live_count == 39
    assert saved[9]                 # the old view is not written through
    _hold([seg])


def test_a_write_through_the_handed_out_array_raises(served):
    seg = _segment(served)
    with pytest.raises(ValueError):
        seg.live[4] = False
    with pytest.raises(ValueError):
        seg.live[:] = False
    assert seg.live_count == 40 == int(np.count_nonzero(seg.live))
    copy = seg.live.copy()          # a reader that needs to write copies
    copy[4] = False
    assert seg.live[4]
