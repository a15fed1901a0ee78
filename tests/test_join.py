"""Parent-child join tests. Reference semantics: modules/parent-join
(ParentJoinFieldMapper, HasChildQueryBuilder, HasParentQueryBuilder,
ParentIdQueryBuilder, inner hits). Ours: shard-global slot space + two-pass
device scatter/gather (search/join.py, plan LHasChild/LHasParent)."""

import pytest

from opensearch_tpu.rest.client import ApiError, RestClient

MAPPING = {"mappings": {"properties": {
    "my_join": {"type": "join", "relations": {"question": ["answer", "comment"]}},
    "title": {"type": "text"},
    "body": {"type": "text"},
    "votes": {"type": "integer"}}}}


@pytest.fixture
def client():
    c = RestClient()
    c.indices.create("j", MAPPING)
    c.index("j", {"title": "how to jit", "my_join": "question"}, id="q1")
    c.index("j", {"title": "sharding question", "my_join": "question"}, id="q2")
    c.index("j", {"title": "lonely question", "my_join": "question"}, id="q3")
    # children must route to the parent's shard
    c.index("j", {"body": "use jax.jit decorator", "votes": 5,
                  "my_join": {"name": "answer", "parent": "q1"}},
            id="a1", routing="q1")
    c.index("j", {"body": "trace once compile once", "votes": 2,
                  "my_join": {"name": "answer", "parent": "q1"}},
            id="a2", routing="q1")
    c.index("j", {"body": "use a mesh", "votes": 7,
                  "my_join": {"name": "answer", "parent": "q2"}},
            id="a3", routing="q2")
    c.index("j", {"body": "nice question", "votes": 1,
                  "my_join": {"name": "comment", "parent": "q2"}},
            id="c1", routing="q2")
    c.indices.refresh("j")
    return c


class TestJoinMapping:
    def test_child_without_routing_rejected(self, client):
        with pytest.raises((ApiError, ValueError)):
            client.index("j", {"my_join": {"name": "answer", "parent": "q1"}},
                         id="bad1")

    def test_child_without_parent_rejected(self, client):
        with pytest.raises((ApiError, ValueError)):
            client.index("j", {"my_join": {"name": "answer"}}, id="bad2",
                         routing="q1")

    def test_unknown_relation_rejected(self, client):
        with pytest.raises((ApiError, ValueError)):
            client.index("j", {"my_join": "reply"}, id="bad3", routing="q1")

    def test_mapping_roundtrip(self, client):
        m = client.indices.get_mapping("j")["j"]["mappings"]
        assert m["properties"]["my_join"]["relations"] == {
            "question": ["answer", "comment"]}

    def test_term_query_on_join_field(self, client):
        r = client.search("j", {"query": {"term": {"my_join": "answer"}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"a1", "a2", "a3"}


class TestHasChild:
    def test_basic_filter(self, client):
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match": {"body": "jit"}}}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["q1"]
        assert r["hits"]["hits"][0]["_score"] == 1.0  # score_mode none

    def test_match_all_children(self, client):
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}}}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"q1", "q2"}

    def test_child_type_isolation(self, client):
        # c1 is a comment, not an answer
        r = client.search("j", {"query": {"has_child": {
            "type": "comment", "query": {"match_all": {}}}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["q2"]

    def test_score_modes(self, client):
        def scores(mode):
            r = client.search("j", {"query": {"has_child": {
                "type": "answer", "score_mode": mode,
                "query": {"function_score": {
                    "query": {"match_all": {}},
                    "functions": [{"script_score": {"script": {
                        "source": "doc['votes'].value"}}}],
                    "boost_mode": "replace"}}}}})
            return {h["_id"]: h["_score"] for h in r["hits"]["hits"]}
        assert scores("sum") == {"q1": 7.0, "q2": 7.0}
        assert scores("max") == {"q1": 5.0, "q2": 7.0}
        assert scores("min") == {"q1": 2.0, "q2": 7.0}
        assert scores("avg") == {"q1": 3.5, "q2": 7.0}

    def test_min_max_children(self, client):
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}}, "min_children": 2}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["q1"]
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}}, "max_children": 1}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["q2"]

    def test_min_children_zero_still_requires_a_match(self, client):
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}}, "min_children": 0}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"q1", "q2"}  # not q3

    def test_bad_score_mode_is_400(self, client):
        with pytest.raises(ApiError):
            client.search("j", {"query": {"has_child": {
                "type": "answer", "query": {"match_all": {}},
                "score_mode": "total"}}})

    def test_second_join_field_rejected(self, client):
        with pytest.raises((ApiError, ValueError)):
            client.indices.create("j2", {"mappings": {"properties": {
                "join_a": {"type": "join", "relations": {"p": ["c"]}},
                "join_b": {"type": "join", "relations": {"x": ["y"]}}}}})

    def test_cross_segment_join(self, client):
        # the new child lands in a different segment than its parent
        client.index("j", {"body": "late jit answer", "votes": 9,
                           "my_join": {"name": "answer", "parent": "q3"}},
                     id="a4", routing="q3")
        client.indices.refresh("j")
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match": {"body": "late"}}}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["q3"]

    def test_deleted_child_stops_matching(self, client):
        client.delete("j", "a3", routing="q2")
        client.indices.refresh("j")
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}}}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"q1"}

    def test_in_bool_with_parent_fields(self, client):
        r = client.search("j", {"query": {"bool": {
            "must": [{"match": {"title": "question"}}],
            "filter": [{"has_child": {"type": "answer",
                                      "query": {"match_all": {}}}}]}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["q2"]

    def test_ignore_unmapped(self, client):
        c = RestClient()
        c.indices.create("plain", {})
        c.index("plain", {"x": 1}, id="1", refresh=True)
        r = c.search("plain", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}},
            "ignore_unmapped": True}}})
        assert r["hits"]["hits"] == []
        with pytest.raises(ApiError):
            c.search("plain", {"query": {"has_child": {
                "type": "answer", "query": {"match_all": {}}}}})

    def test_inner_hits(self, client):
        r = client.search("j", {"query": {"has_child": {
            "type": "answer", "query": {"match_all": {}},
            "score_mode": "sum", "inner_hits": {}}}})
        by_id = {h["_id"]: h for h in r["hits"]["hits"]}
        ih = by_id["q1"]["inner_hits"]["answer"]["hits"]
        assert ih["total"]["value"] == 2
        assert {hh["_id"] for hh in ih["hits"]} == {"a1", "a2"}

    def test_explain_matches_score(self, client):
        r = client.search("j", {"explain": True,
                                "query": {"has_child": {
                                    "type": "answer", "score_mode": "sum",
                                    "query": {"function_score": {
                                        "query": {"match_all": {}},
                                        "functions": [{"script_score": {"script": {
                                            "source": "doc['votes'].value"}}}],
                                        "boost_mode": "replace"}}}}})
        for h in r["hits"]["hits"]:
            assert h["_explanation"]["value"] == pytest.approx(h["_score"], rel=1e-5)


class TestHasParent:
    def test_basic(self, client):
        r = client.search("j", {"query": {"has_parent": {
            "parent_type": "question", "query": {"match": {"title": "jit"}}}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"a1", "a2"}
        assert all(h["_score"] == 1.0 for h in r["hits"]["hits"])

    def test_all_child_types_match(self, client):
        r = client.search("j", {"query": {"has_parent": {
            "parent_type": "question",
            "query": {"match": {"title": "sharding"}}}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"a3", "c1"}

    def test_score_true(self, client):
        r = client.search("j", {"query": {"has_parent": {
            "parent_type": "question", "score": True,
            "query": {"function_score": {
                "query": {"match_all": {}},
                "functions": [{"weight": 3.0}],
                "boost_mode": "replace"}}}}})
        assert all(h["_score"] == 3.0 for h in r["hits"]["hits"])
        assert {h["_id"] for h in r["hits"]["hits"]} == {"a1", "a2", "a3", "c1"}

    def test_inner_hits(self, client):
        r = client.search("j", {"query": {"has_parent": {
            "parent_type": "question", "query": {"match": {"title": "jit"}},
            "inner_hits": {}}}})
        h = next(x for x in r["hits"]["hits"] if x["_id"] == "a1")
        ih = h["inner_hits"]["question"]["hits"]
        assert ih["total"]["value"] == 1
        assert ih["hits"][0]["_id"] == "q1"


class TestParentId:
    def test_basic(self, client):
        r = client.search("j", {"query": {"parent_id": {
            "type": "answer", "id": "q1"}}})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"a1", "a2"}

    def test_type_filtering(self, client):
        r = client.search("j", {"query": {"parent_id": {
            "type": "comment", "id": "q2"}}})
        assert [h["_id"] for h in r["hits"]["hits"]] == ["c1"]


class TestJoinMultiShard:
    def test_routing_keeps_family_together(self):
        c = RestClient()
        c.indices.create("jm", {**MAPPING, "settings": {"number_of_shards": 4}})
        for i in range(6):
            c.index("jm", {"title": f"question {i}", "my_join": "question"},
                    id=f"q{i}")
            c.index("jm", {"body": f"answer {i}", "votes": i,
                           "my_join": {"name": "answer", "parent": f"q{i}"}},
                    id=f"a{i}", routing=f"q{i}")
        c.indices.refresh("jm")
        r = c.search("jm", {"query": {"has_child": {
            "type": "answer", "query": {"range": {"votes": {"gte": 4}}}}},
            "size": 20})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"q4", "q5"}
        r = c.search("jm", {"query": {"has_parent": {
            "parent_type": "question", "query": {"match": {"title": "3"}}}},
            "size": 20})
        assert {h["_id"] for h in r["hits"]["hits"]} == {"a3"}
