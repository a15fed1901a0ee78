"""Depth items: full termvectors/mtermvectors, nodes_stats, tracing,
profile plan tree, can_match breadth.

References: action/termvectors/TermVectorsRequest.java,
action/admin/cluster/node/stats/, telemetry/tracing/Tracer.java,
search/profile/ProfileResult.java, CanMatchPreFilterSearchPhase.java."""

import pytest

from opensearch_tpu.rest.client import ApiError, RestClient


@pytest.fixture
def client():
    c = RestClient()
    c.indices.create("d", body={"mappings": {"properties": {
        "txt": {"type": "text"},
        "kw": {"type": "keyword"},
        "n": {"type": "integer"}}}})
    c.index("d", {"txt": "the quick brown fox the fox", "kw": "k1", "n": 1},
            id="1")
    c.index("d", {"txt": "lazy dog sleeps", "kw": "k2", "n": 2}, id="2",
            refresh=True)
    return c


class TestTermvectors:
    def test_tokens_positions_offsets(self, client):
        r = client.termvectors("d", "1", fields=["txt"])
        terms = r["term_vectors"]["txt"]["terms"]
        assert terms["fox"]["term_freq"] == 2
        toks = terms["quick"]["tokens"][0]
        assert toks["position"] == 1
        assert toks["start_offset"] == 4 and toks["end_offset"] == 9

    def test_term_statistics(self, client):
        r = client.termvectors("d", "1", body={"term_statistics": True,
                                               "fields": ["txt"]})
        t = r["term_vectors"]["txt"]["terms"]["fox"]
        assert t["doc_freq"] == 1 and t["ttf"] == 2

    def test_field_statistics(self, client):
        r = client.termvectors("d", "1", fields=["txt"])
        fs = r["term_vectors"]["txt"]["field_statistics"]
        assert fs["doc_count"] == 2
        assert fs["sum_ttf"] >= 8

    def test_keyword_field(self, client):
        r = client.termvectors("d", "1", fields=["kw"])
        assert r["term_vectors"]["kw"]["terms"] == {"k1": {"term_freq": 1}}

    def test_artificial_doc(self, client):
        r = client.termvectors("d", body={
            "doc": {"txt": "brand new words fox"}, "fields": ["txt"]})
        assert "fox" in r["term_vectors"]["txt"]["terms"]
        assert "new" in r["term_vectors"]["txt"]["terms"]

    def test_filter_max_num_terms(self, client):
        r = client.termvectors("d", "1", body={
            "fields": ["txt"], "filter": {"max_num_terms": 2}})
        terms = r["term_vectors"]["txt"]["terms"]
        assert len(terms) == 2
        assert all("score" in t for t in terms.values())
        # fox (tf=2, df=1) must survive the tf-idf ranking
        assert "fox" in terms

    def test_missing_doc(self, client):
        r = client.termvectors("d", "zzz")
        assert r["found"] is False

    def test_mtermvectors(self, client):
        r = client.mtermvectors({"docs": [
            {"_index": "d", "_id": "1", "fields": ["txt"]},
            {"_index": "d", "_id": "2", "fields": ["txt"]}]})
        assert len(r["docs"]) == 2
        assert "fox" in r["docs"][0]["term_vectors"]["txt"]["terms"]
        assert "dog" in r["docs"][1]["term_vectors"]["txt"]["terms"]


class TestNodesStats:
    def test_shape_and_counters(self, client):
        client.search("d", {"query": {"match": {"txt": "fox"}}})
        client.get("d", "1")
        r = client.nodes_stats()
        nb = r["nodes"][client.node.node_name]
        assert nb["indices"]["docs"]["count"] == 2
        assert nb["indices"]["search"]["query_total"] >= 1
        assert nb["indices"]["indexing"]["index_total"] >= 2
        assert nb["indices"]["get"]["total"] >= 1
        assert nb["process"]["mem"]["resident_set_size_in_bytes"] > 0
        assert "thread_pool" in nb and "breakers" in nb
        assert nb["indices"]["store"]["size_in_bytes"] > 0


class TestTracing:
    def test_search_trace_recorded(self, client):
        client.node.tracer._traces.clear()
        client.search("d", {"query": {"match": {"txt": "fox"}}})
        traces = client.get_traces()["traces"]
        assert traces, "no trace recorded"
        root = traces[0]
        assert root["name"] == "rest.search"
        (coord,) = root["children"]
        assert coord["name"] == "indices:data/read/search"
        assert coord["trace_id"] == root["trace_id"] == root["span_id"]
        names = {c["name"] for c in coord.get("children", [])}
        assert "query_phase" in names
        assert root["duration_ms"] >= coord["duration_ms"] >= 0

    def test_tracer_stats_in_node_stats(self, client):
        st = client.nodes_stats()["nodes"][client.node.node_name]
        assert st["tracing"]["enabled"] is True


class TestProfilePlanTree:
    def test_profile_has_plan_tree(self, client):
        r = client.search("d", {"profile": True, "query": {"bool": {
            "must": [{"match": {"txt": "fox"}}],
            "filter": [{"range": {"n": {"gte": 0}}}]}}})
        shards = r["profile"]["shards"]
        assert shards
        q = shards[0]["searches"][0]["query"]
        assert q and q[0]["type"] == "Bool"
        kinds = {c["type"] for c in q[0]["children"]}
        assert "Terms" in kinds and "Range" in kinds
        assert q[0]["time_in_nanos"] > 0
        assert shards[0]["searches"][0]["collector"]


class TestCanMatchBreadth:
    def test_new_kinds(self, client):
        from opensearch_tpu.search import compiler as C, plan as PL
        from opensearch_tpu.search import query_dsl as dsl
        svc = client.node.get_index("d")
        seg = svc.shards[0].segments[0]
        ctx = PL.ShardContext(svc.mappings, [seg], svc.default_sim, {})

        def cm(q):
            return C.can_match(PL.rewrite(dsl.parse_query(q), ctx, True), seg)

        assert cm({"exists": {"field": "txt"}})
        assert not cm({"exists": {"field": "ghost"}})
        assert cm({"ids": {"values": ["1"]}})
        assert not cm({"ids": {"values": ["zzz"]}})
        assert not cm({"knn": {"ghostvec": {"vector": [1.0], "k": 1}}})
        assert cm({"dis_max": {"queries": [{"term": {"kw": "k1"}}]}})
        assert not cm({"geo_distance": {"distance": "1km",
                                        "ghost": {"lat": 0, "lon": 0}}})


class TestStoredFields:
    def test_store_true_and_source_disabled(self, tmp_path):
        c = RestClient(data_path=str(tmp_path / "d"))
        c.indices.create("st", body={"mappings": {
            "_source": {"enabled": False},
            "properties": {
                "title": {"type": "text", "store": True},
                "hidden": {"type": "keyword"}}}})
        c.index("st", {"title": "kept around", "hidden": "gone"}, id="1",
                refresh=True)
        r = c.search("st", {"query": {"match": {"title": "kept"}},
                            "stored_fields": ["title", "hidden"]})
        h = r["hits"]["hits"][0]
        assert "_source" not in h          # _source disabled
        assert h["fields"]["title"] == ["kept around"]
        assert "hidden" not in h["fields"]  # not store=true
        # hidden is still SEARCHABLE (indexed), just not stored
        r2 = c.search("st", {"query": {"term": {"hidden": "gone"}}})
        assert r2["hits"]["total"]["value"] == 1
        assert r2["hits"]["hits"][0].get("_source") in (None, {})

    def test_stored_fields_suppress_source_by_default(self, client):
        c = client
        r = c.search("d", {"query": {"ids": {"values": ["1"]}},
                           "stored_fields": ["txt"]})
        assert "_source" not in r["hits"]["hits"][0]
        r = c.search("d", {"query": {"ids": {"values": ["1"]}},
                           "stored_fields": ["txt"], "_source": True})
        assert "_source" in r["hits"]["hits"][0]

    def test_stored_survives_flush_and_merge(self, tmp_path):
        path = str(tmp_path / "d2")
        c = RestClient(data_path=path)
        c.indices.create("sm", body={
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {
                "v": {"type": "keyword", "store": True}}}})
        c.index("sm", {"v": "one"}, id="1")
        c.indices.refresh("sm")
        c.index("sm", {"v": "two"}, id="2")
        c.indices.refresh("sm")
        c.indices.forcemerge("sm")
        c.indices.flush("sm")
        c2 = RestClient(data_path=path)
        r = c2.search("sm", {"query": {"match_all": {}},
                             "stored_fields": ["v"],
                             "sort": [{"v": "asc"}]})
        assert [h["fields"]["v"] for h in r["hits"]["hits"]] == \
            [["one"], ["two"]]


class TestValidateQuery:
    def test_valid_and_invalid(self, client):
        r = client.validate_query("d", {"query": {"match": {"txt": "fox"}}})
        assert r["valid"] is True
        r = client.validate_query("d", {"query": {"bogus_kind": {}}},
                                  explain=True)
        assert r["valid"] is False
        assert "bogus_kind" in r["explanations"][0]["error"]

    def test_explain_shows_rewritten(self, client):
        r = client.validate_query("d", {"query": {"match": {"txt": "fox"}}},
                                  explain=True)
        assert r["valid"] and "Terms" in r["explanations"][0]["explanation"]

    def test_validate_verdict_independent_of_flags(self, client):
        # rewrite-stage failure detected with AND without explain
        bad = {"query": {"regexp": {"txt": "(unclosed"}}}
        assert client.validate_query("d", bad)["valid"] is False
        r = client.validate_query("d", bad, explain=True)
        assert r["valid"] is False and r["explanations"][0]["valid"] is False

    def test_validate_missing_index_404(self, client):
        with pytest.raises(ApiError) as ei:
            client.validate_query("ghost-idx", {"query": {"match_all": {}}})
        assert ei.value.status == 404

    def test_validate_rewrite_flag_shows_plan(self, client):
        r = client.validate_query("d", {"query": {"match": {"txt": "fox"}}},
                                  rewrite=True)
        assert r["explanations"][0]["explanation"].startswith("Terms")
