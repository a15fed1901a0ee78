"""Field mappings and document parsing. Analog of reference
`server/src/main/java/org/opensearch/index/mapper/` (MapperService,
DocumentMapper, TextFieldMapper, KeywordFieldMapper, NumberFieldMapper,
DateFieldMapper, BooleanFieldMapper, IpFieldMapper, GeoPointFieldMapper,
ObjectMapper, FieldAliasMapper, dynamic templates).

Documents are parsed on the host into per-field term lists (indexed fields)
and doc-value scalars (columnar fields); the device only ever sees integer
term rows and numeric columns.
"""

from __future__ import annotations

import datetime as _dt
import ipaddress
import numbers
import re
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

from ..analysis import AnalysisRegistry, Analyzer
from .date_formats import DateFormatError, parse_date, validate_format

TEXT_TYPES = {"text", "match_only_text", "search_as_you_type",
              "annotated_text"}
KEYWORD_TYPES = {"keyword", "ip", "constant_keyword", "flat_object",
                 "icu_collation_keyword"}
INT_TYPES = {"long", "integer", "short", "byte", "date", "boolean",
             "unsigned_long", "token_count"}
FLOAT_TYPES = {"double", "float", "half_float", "rank_feature",
               "scaled_float"}
NUMERIC_TYPES = INT_TYPES | FLOAT_TYPES
# range family (reference RangeFieldMapper): stored as closed [lo, hi]
# interval columns `field#lo` / `field#hi` in the member type's column
# representation; queried with relation intersects/within/contains
RANGE_TYPES = {"integer_range", "long_range", "float_range", "double_range",
               "date_range", "ip_range"}
RANGE_MEMBER = {"integer_range": "integer", "long_range": "long",
                "float_range": "float", "double_range": "double",
                "date_range": "date", "ip_range": "ip"}
# unsigned_long stores order-preserving BIASED i64 (v - 2^63) so 64-bit
# compares/sorts stay exact; the device f32 view and fetch unbias
# (reference UnsignedLongFieldMapper shifts the same way)
U64_BIAS = 1 << 63
GEO_TYPES = {"geo_point"}
SHAPE_TYPES = {"geo_shape"}
VECTOR_TYPES = {"dense_vector", "knn_vector"}
# a vector field's space, by the k-NN plugin's names and this repo's, as
# the one name the scorer knows (`compiler.emit`'s `_sim_score`)
VECTOR_SPACES = {"l2": "l2_norm", "innerproduct": "dot_product",
                 "cosinesimil": "cosine", "l2_norm": "l2_norm",
                 "dot_product": "dot_product", "cosine": "cosine"}
# feature-weight CSR fields (reference mapper-extras RankFeaturesFieldMapper;
# sparse_vector is the same storage with learned-sparse token weights)
FEATURE_TYPES = {"rank_features", "sparse_vector"}


class VectorMappingError(ValueError):
    """A vector field's space or method this engine does not have (a
    client error: 400)."""


@dataclass
class FieldType:
    name: str
    type: str
    analyzer: str = "standard"
    search_analyzer: Optional[str] = None
    normalizer: Optional[str] = None
    index: bool = True
    doc_values: bool = True
    store: bool = False
    null_value: Any = None
    ignore_above: Optional[int] = None
    copy_to: List[str] = dc_field(default_factory=list)
    date_format: Optional[str] = None
    boost: float = 1.0
    dims: int = 0                       # dense_vector dimension
    vector_similarity: str = "cosine"   # cosine | dot_product | l2_norm
    # ANN method (reference k-NN plugin `method` / ES `index_options`):
    # normalized to {"name": "ivf", "nlist": int|None, "nprobe": int|None};
    # None = exact brute-force scan (the default)
    vector_method: Optional[dict] = None
    # join field (reference modules/parent-join ParentJoinFieldMapper):
    # {"parent_relation": ["child_relation", ...]}
    relations: Dict[str, List[str]] = dc_field(default_factory=dict)
    # rank_feature(s): False flips the scoring functions (reference
    # RankFeatureFieldMapper positive_score_impact)
    positive_score_impact: bool = True
    # text fields keep norms (doc length) unless disabled; keyword fields never
    norms: bool = True
    subfields: Dict[str, "FieldType"] = dc_field(default_factory=dict)
    # scaled_float (mapper-extras ScaledFloatFieldMapper): values quantize
    # to round(v * scaling_factor) / scaling_factor
    scaling_factor: Optional[float] = None
    # constant_keyword (ConstantKeywordFieldMapper): the index-wide value
    # (from the mapping, or adopted from the first document that sets it)
    const_value: Optional[str] = None
    # synthetic flat_object leaf (FlatObjectFieldMapper ._valueAndPath):
    # query terms become "<flat_prefix>=<value>" against `<root>#paths`
    flat_prefix: Optional[str] = None
    # term_vector: "with_positions_offsets" persists per-doc (term, pos,
    # start, end) for the real FastVectorHighlighter path
    term_vector: str = "no"
    # rank_features/sparse_vector opt-in: build a codec-v2 FEATURE
    # impact plane (quantized model-assigned weights + block-max
    # sidecar) so neural_sparse serves through the impact ladder
    # (search/impactpath.py, docs/HYBRID.md)
    index_impacts: bool = False

    @property
    def is_indexed_terms(self) -> bool:
        return self.index and (self.type in TEXT_TYPES or self.type in KEYWORD_TYPES)

    @property
    def has_norms(self) -> bool:
        return self.type in TEXT_TYPES and self.norms and \
            self.type != "match_only_text"


_ANNOT_RE = re.compile(r"\[([^\]]*)\]\(([^)]+)\)")


def parse_annotated_text(raw: str):
    """-> (plain_text, [(char_start, char_end, [annotation values])]).

    Markup follows the reference plugin (mapper-annotated-text): the covered
    text appears in the plain stream; `&`-separated, URL-encoded annotation
    values attach to its character span."""
    import urllib.parse as _up
    plain_parts = []
    spans = []
    pos = 0
    last = 0
    for m in _ANNOT_RE.finditer(raw):
        plain_parts.append(raw[last:m.start()])
        pos += m.start() - last
        text = m.group(1)
        anns = [_up.unquote(a) for a in m.group(2).split("&") if a]
        spans.append((pos, pos + len(text), anns))
        plain_parts.append(text)
        pos += len(text)
        last = m.end()
    plain_parts.append(raw[last:])
    return "".join(plain_parts), spans


# epoch millis of a date value under a format (reference DateFieldMapper;
# default `strict_date_optional_time||epoch_millis`): the patterns, and the
# rounding of the parts a text leaves out, are `date_formats.parse_date`'s
_parse_date = parse_date


def _checked_date_format(path: str, ftype: str, cfg: dict) -> Optional[str]:
    """A mapping's `format`, refused where this engine cannot read one of
    its patterns (a date field would otherwise index under another)."""
    fmt = cfg.get("format")
    if fmt is not None and ftype in ("date", "date_nanos", "date_range"):
        try:
            validate_format(fmt)
        except DateFormatError as e:
            raise DateFormatError(f"field [{path}]: {e}")
    return fmt


def _ip_to_int(value: str) -> int:
    """IPs index as integers (v4 mapped into v6 space like Lucene InetAddressPoint)."""
    ip = ipaddress.ip_address(value)
    if isinstance(ip, ipaddress.IPv4Address):
        ip = ipaddress.IPv6Address(f"::ffff:{value}")
    return int(ip)


def coerce_value(ft: FieldType, value: Any):
    """Coerce a raw JSON value to the column representation: ints for the long
    family (dates→millis, bool→0/1, ip→int), floats for the float family."""
    t = ft.type
    if t == "date":
        return _parse_date(value, ft.date_format)
    if t == "boolean":
        if isinstance(value, str):
            if value in ("true", "True"):
                return 1
            if value in ("false", "False", ""):
                return 0
            raise ValueError(f"cannot parse boolean [{value}]")
        return 1 if bool(value) else 0
    if t == "ip":
        return _ip_to_int(str(value))
    if t == "unsigned_long":
        iv = int(value)
        if not 0 <= iv < (1 << 64):
            raise ValueError(
                f"value [{value}] out of range for field type [unsigned_long]")
        return iv - U64_BIAS
    if t in INT_TYPES:
        iv = int(value)
        limits = {"long": 63, "integer": 31, "short": 15, "byte": 7}
        bits = limits.get(t, 63)
        if not (-(1 << bits)) <= iv < (1 << bits):
            raise ValueError(f"value [{value}] out of range for field type [{t}]")
        return iv
    if t == "scaled_float":
        sf = ft.scaling_factor or 1.0
        return round(float(value) * sf) / sf
    if t in FLOAT_TYPES:
        fv = float(value)
        if t == "rank_feature" and fv <= 0:
            raise ValueError(
                f"[rank_feature] fields must hold positive values, got [{fv}]")
        return fv
    raise ValueError(f"cannot coerce for type [{t}]")


@dataclass
class ParsedDocument:
    """Index-ready view of one document (analog of reference ParsedDocument)."""

    doc_id: str
    source: dict
    routing: Optional[str]
    # field -> list of terms (text: analyzed tokens incl. duplicates for tf;
    # keyword: normalized exact values)
    terms: Dict[str, List[str]] = dc_field(default_factory=dict)
    # field -> list of (term, position) for positional indexes
    positions: Dict[str, List[Tuple[str, int]]] = dc_field(default_factory=dict)
    # field -> per-VALUE lists of (term, position, start_offset,
    # end_offset) for term_vector=with_positions_offsets fields (FVH);
    # offsets are relative to their own value string
    offsets: Dict[str, List[List[Tuple[str, int, int, int]]]] = dc_field(default_factory=dict)
    # field -> raw values for store=true fields (reference stored fields)
    stored: Dict[str, list] = dc_field(default_factory=dict)
    # field -> list of numeric values (column stores the first; extra values
    # still participate in term-style matching for the long family)
    numerics: Dict[str, List[Any]] = dc_field(default_factory=dict)
    # field -> list of keyword strings for doc values (terms agg / sort)
    keywords: Dict[str, List[str]] = dc_field(default_factory=dict)
    # field -> list of (lat, lon)
    geos: Dict[str, List[Tuple[float, float]]] = dc_field(default_factory=dict)
    # field -> list of geo_shape specs (GeoJSON dict / WKT string, validated)
    shapes: Dict[str, List[Any]] = dc_field(default_factory=dict)
    # field -> vector (one per doc)
    vectors: Dict[str, List[float]] = dc_field(default_factory=dict)
    # nested path -> child ParsedDocuments (block-join children; reference
    # NestedObjectMapper creates separate Lucene docs in the parent's block)
    nested: Dict[str, List["ParsedDocument"]] = dc_field(default_factory=dict)
    # field -> {feature: weight} (rank_features / sparse_vector)
    features: Dict[str, Dict[str, float]] = dc_field(default_factory=dict)


class Mappings:
    """Per-index mappings with dynamic mapping (reference MapperService).

    Construction takes the `{"properties": {...}}` mapping dict; unseen fields
    encountered at parse time are dynamically mapped (string→text+`.keyword`
    subfield, int→long, float→double, bool→boolean, dict→object) exactly like
    the reference's default dynamic rules.
    """

    def __init__(self, mapping: dict | None = None, analysis: AnalysisRegistry | None = None,
                 dynamic: bool | str = True):
        self.analysis = analysis or AnalysisRegistry()
        self.fields: Dict[str, FieldType] = {}
        self.aliases: Dict[str, str] = {}
        self.nested_paths: set = set()
        self.join_field: Optional[str] = None  # at most one per index (like reference)
        self.dynamic = dynamic
        self.dynamic_templates: List[dict] = []
        self.derived: Dict[str, Any] = {}   # name -> DerivedField
        self.star_trees: List[Any] = []     # StarTreeConfig (search/startree)
        self._meta: dict = {}
        # reference SourceFieldMapper: `"_source": {"enabled": false}` stops
        # persisting _source in segments (store=true fields remain fetchable
        # via stored_fields; update/reindex lose their input, as upstream)
        self.source_enabled = True
        # plugins/mapper-size SizeFieldMapper: `"_size": {"enabled": true}`
        # indexes the byte length of _source as numeric doc values
        self.size_enabled = False
        if mapping:
            self.merge(mapping)

    # ---------------- mapping CRUD ----------------

    def merge(self, mapping: dict) -> None:
        if "dynamic" in mapping:
            self.dynamic = mapping["dynamic"]
        if "_meta" in mapping:
            self._meta.update(mapping["_meta"])
        if "_source" in mapping:
            self.source_enabled = bool(mapping["_source"].get("enabled", True))
        if "_size" in mapping:
            self.size_enabled = bool(mapping["_size"].get("enabled", False))
            if self.size_enabled and "_size" not in self.fields:
                self.fields["_size"] = FieldType(name="_size", type="long",
                                                 index=False)
        self.dynamic_templates.extend(mapping.get("dynamic_templates", []))
        self._merge_props(mapping.get("properties", {}), prefix="")
        if "derived" in mapping:
            # derived (runtime) fields: scripts evaluated per segment at
            # query time (search/derived.py; reference DerivedFieldMapper)
            from ..search.derived import check_conflicts, parse_defs
            defs = parse_defs(mapping["derived"])
            check_conflicts(self, defs)
            self.derived.update(defs)

    def _merge_props(self, props: dict, prefix: str) -> None:
        for name, cfg in props.items():
            path = f"{prefix}{name}"
            ftype = cfg.get("type", "object" if "properties" in cfg else "text")
            if ftype == "alias":
                self.aliases[path] = cfg["path"]
                continue
            if ftype in ("object", "nested"):
                if ftype == "nested":
                    self.nested_paths.add(path)
                self._merge_props(cfg.get("properties", {}), prefix=f"{path}.")
                continue
            if ftype == "star_tree":
                # composite pre-agg cube config (search/startree.py;
                # reference StarTreeMapper) — config-only, no doc values
                from ..search.startree import parse_config
                self.star_trees.append(parse_config(path, cfg))
                continue
            self.fields[path] = self._build_field(path, ftype, cfg)
            if ftype == "join":
                if self.join_field is not None and self.join_field != path:
                    raise ValueError(
                        f"only one [join] field can be defined per index, "
                        f"found [{self.join_field}] and [{path}]")
                self.join_field = path

    def _build_field(self, path: str, ftype: str, cfg: dict) -> FieldType:
        normalizer = cfg.get("normalizer")
        if ftype == "icu_collation_keyword":
            # reference ICUCollationKeywordFieldMapper
            # (plugins/analysis-icu): values index and doc-value as
            # collation SORT KEYS, so term queries / sorting / aggs all
            # operate in collation space. `language`/`country` accepted
            # for API parity; key construction is locale-independent
            # (strength cascade approximated; see unicode_plugins)
            strength = cfg.get("strength", "tertiary")
            if strength not in ("primary", "secondary", "tertiary"):
                raise ValueError(
                    f"[icu_collation_keyword] field [{path}]: unsupported "
                    f"strength [{strength}] (supported: primary, "
                    f"secondary, tertiary)")
            normalizer = f"_icu_collation:{strength}"
        ft = FieldType(
            name=path, type=ftype,
            analyzer=cfg.get("analyzer", "standard"),
            search_analyzer=cfg.get("search_analyzer"),
            normalizer=normalizer,
            index=cfg.get("index", True),
            doc_values=cfg.get("doc_values", True),
            store=cfg.get("store", False),
            null_value=cfg.get("null_value"),
            ignore_above=cfg.get("ignore_above"),
            copy_to=list(cfg.get("copy_to", []) if isinstance(cfg.get("copy_to", []), list)
                         else [cfg["copy_to"]]),
            date_format=_checked_date_format(path, ftype, cfg),
            term_vector=cfg.get("term_vector", "no"),
            boost=cfg.get("boost", 1.0),
            norms=cfg.get("norms", True),
            dims=int(cfg.get("dims", cfg.get("dimension", 0))),
        )
        if ftype in VECTOR_TYPES:
            method = cfg.get("method") or cfg.get("index_options")
            # OpenSearch 2.x carries `space_type` inside `method`; the
            # field's own key (1.x, and this repo's `similarity`) wins
            space = cfg.get("similarity", cfg.get(
                "space_type", (method or {}).get("space_type", "cosine")))
            if space not in VECTOR_SPACES:
                raise VectorMappingError(
                    f"unknown space_type [{space}] for field [{path}] "
                    f"(supported: {', '.join(VECTOR_SPACES)})")
            ft.vector_similarity = VECTOR_SPACES[space]
            if method:
                # `engine` and HNSW's `parameters` (m, ef_construction,
                # ef_search) ride along unread: IVF's are nlist / nprobe
                name = method.get("name", method.get("type", "ivf"))
                if name not in ("ivf", "flat", "exact"):
                    raise VectorMappingError(
                        f"unknown ANN method [{name}] for field [{path}] "
                        f"(supported: ivf, flat)")
                if name == "ivf":
                    p = method.get("parameters") or method
                    ft.vector_method = {
                        "name": "ivf",
                        "nlist": (int(p["nlist"]) if p.get("nlist") else None),
                        "nprobe": (int(p["nprobe"]) if p.get("nprobe")
                                   else None)}
        if ftype == "join":
            ft.relations = {p: (c if isinstance(c, list) else [c])
                            for p, c in cfg.get("relations", {}).items()}
        ft.positive_score_impact = bool(cfg.get("positive_score_impact", True))
        if "index_impacts" in cfg:
            if ftype not in FEATURE_TYPES:
                raise ValueError(
                    f"Field [{path}]: [index_impacts] only applies to "
                    f"rank_features/sparse_vector fields")
            ft.index_impacts = bool(cfg["index_impacts"])
        if ftype == "scaled_float":
            if "scaling_factor" not in cfg:
                raise ValueError(
                    f"Field [{path}] misses required parameter "
                    f"[scaling_factor]")
            ft.scaling_factor = float(cfg["scaling_factor"])
        if ftype == "constant_keyword":
            if cfg.get("value") is not None:
                ft.const_value = str(cfg["value"])
        if ftype == "search_as_you_type":
            # reference SearchAsYouTypeFieldMapper: main field + shingle
            # subfields + an edge-ngram prefix field for bool_prefix
            shingles = int(cfg.get("max_shingle_size", 3))
            self.analysis.ensure_sayt_chains(shingles)
            for n in range(2, shingles + 1):
                ft.subfields[f"_{n}gram"] = FieldType(
                    name=f"{path}._{n}gram", type="text",
                    analyzer=f"__sayt_{n}gram")
            ft.subfields["_index_prefix"] = FieldType(
                name=f"{path}._index_prefix", type="text",
                analyzer="__sayt_prefix",
                search_analyzer=cfg.get("analyzer", "standard"))
        for sub, subcfg in cfg.get("fields", {}).items():
            ft.subfields[sub] = self._build_field(f"{path}.{sub}", subcfg.get("type", "keyword"), subcfg)
        return ft

    def to_dict(self) -> dict:
        props: dict = {}
        for path, ft in self.fields.items():
            node = props
            parts = path.split(".")
            # reconstruct nested properties for object paths
            skip = False
            for p in parts[:-1]:
                if f"{'.'.join(parts[:parts.index(p)+1])}" in self.fields:
                    skip = True  # dotted subfield of a mapped field, not an object
                    break
                node = node.setdefault(p, {}).setdefault("properties", {})
            if skip:
                continue
            d: dict = {"type": ft.type}
            if ft.relations:
                d["relations"] = ft.relations
            if ft.type == "text" and ft.analyzer != "standard":
                d["analyzer"] = ft.analyzer
            if ft.type == "icu_collation_keyword":
                # round-trip the strength PARAM, not the internal
                # normalizer name (feeding the mapping back into create
                # must reproduce the same field)
                d["strength"] = (ft.normalizer or "_icu_collation:tertiary"
                                 ).split(":", 1)[1]
            elif ft.normalizer:
                d["normalizer"] = ft.normalizer
            if not ft.index:
                d["index"] = False
            # what decides how a value is read and stored round-trips
            # (`get_mapping`, and the persisted metadata a restart reads)
            if ft.date_format is not None:
                d["format"] = ft.date_format
            if ft.scaling_factor is not None:
                d["scaling_factor"] = ft.scaling_factor
            if ft.subfields:
                d["fields"] = {s: {"type": sf.type} for s, sf in ft.subfields.items()}
            node[parts[-1]] = d
        for npath in sorted(self.nested_paths):
            parts = npath.split(".")
            node = props
            for p in parts[:-1]:
                node = node.setdefault(p, {}).setdefault("properties", {})
            node.setdefault(parts[-1], {})["type"] = "nested"
        out = {"properties": props}
        if self.derived:
            out["derived"] = {
                n: {"type": d.type, "script": {"source": d.source},
                    **({"format": d.fmt} if d.fmt else {})}
                for n, d in self.derived.items()}
        if self._meta:
            out["_meta"] = self._meta
        if not self.source_enabled:
            out["_source"] = {"enabled": False}
        return out

    # ---------------- field resolution ----------------

    def resolve_field(self, name: str) -> Optional[FieldType]:
        name = self.aliases.get(name, name)
        ft = self.fields.get(name)
        if ft is not None:
            return ft
        # multi-field lookup: "title.keyword"
        if "." in name:
            parent, sub = name.rsplit(".", 1)
            parent = self.aliases.get(parent, parent)
            pft = self.fields.get(parent)
            if pft and sub in pft.subfields:
                return pft.subfields[sub]
            # flat_object leaf: "f.a.b" -> term "a.b=<v>" on "f#paths"
            # (reference FlatObjectFieldMapper ._valueAndPath field)
            parts = name.split(".")
            for i in range(1, len(parts)):
                root = ".".join(parts[:i])
                rft = self.fields.get(root)
                if rft is not None and rft.type == "flat_object":
                    sub_path = ".".join(parts[i:])
                    return FieldType(name=f"{root}#paths", type="keyword",
                                     flat_prefix=sub_path)
        df = self.derived.get(name)
        if df is not None:
            return FieldType(name=name, type=df.type, date_format=df.fmt)
        return None

    def index_analyzer(self, ft: FieldType) -> Analyzer:
        if ft.type in KEYWORD_TYPES:
            return self.analysis.normalizer(ft.normalizer)
        return self.analysis.get(ft.analyzer)

    def search_analyzer_for(self, ft: FieldType) -> Analyzer:
        if ft.type in KEYWORD_TYPES:
            return self.analysis.normalizer(ft.normalizer)
        return self.analysis.get(ft.search_analyzer or ft.analyzer)

    # ---------------- dynamic mapping ----------------

    def _dynamic_type(self, path: str, value: Any) -> Optional[FieldType]:
        for tmpl in self.dynamic_templates:
            rule = next(iter(tmpl.values()))
            match = rule.get("match", "*")
            import fnmatch
            if fnmatch.fnmatch(path.split(".")[-1], match):
                cfg = dict(rule.get("mapping", {}))
                return self._build_field(path, cfg.get("type", "text"), cfg)
        if isinstance(value, bool):
            return self._build_field(path, "boolean", {})
        if isinstance(value, int):
            return self._build_field(path, "long", {})
        if isinstance(value, float):
            return self._build_field(path, "double", {})
        if isinstance(value, str):
            # try date detection like reference's date_detection (ISO only)
            try:
                _dt.datetime.fromisoformat(value.replace("Z", "+00:00"))
                return self._build_field(path, "date", {})
            except ValueError:
                pass
            return self._build_field(path, "text",
                                     {"fields": {"keyword": {"type": "keyword",
                                                             "ignore_above": 256}}})
        return None

    # ---------------- document parsing ----------------

    def parse(self, doc_id: str, source: dict, routing: Optional[str] = None) -> ParsedDocument:
        parsed = ParsedDocument(doc_id=doc_id, source=source, routing=routing)
        self._parse_obj(source, "", parsed)
        # constant_keyword fields apply to EVERY document once a value is
        # known (reference ConstantKeywordFieldMapper)
        for ft in self.fields.values():
            if ft.type == "constant_keyword" and ft.const_value is not None:
                parsed.terms.setdefault(ft.name, []).append(ft.const_value)
                parsed.keywords.setdefault(ft.name, []).append(ft.const_value)
        if self.size_enabled:
            import json as _json
            parsed.numerics["_size"] = [len(_json.dumps(
                source, separators=(",", ":"), default=str).encode("utf-8"))]
        return parsed

    def _parse_obj(self, obj: dict, prefix: str, parsed: ParsedDocument) -> None:
        for key, value in obj.items():
            path = f"{prefix}{key}"
            if path in self.nested_paths:
                # block-join children: each object indexes as its own child
                # doc (fields keep their full dotted path), attached to the
                # nearest enclosing doc — multi-level nested paths therefore
                # attach grandchildren to their child doc, and build_segment's
                # recursion gives every level its own block
                if value is None:
                    continue  # explicit null nested value == missing
                children = value if isinstance(value, list) else [value]
                bucket = parsed.nested.setdefault(path, [])
                for child_obj in children:
                    if child_obj is None:
                        continue
                    if not isinstance(child_obj, dict):
                        raise ValueError(
                            f"object mapping for [{path}] tried to parse a "
                            f"non-object value")
                    child = ParsedDocument(
                        doc_id=f"{parsed.doc_id}#{path}#{len(bucket)}",
                        source=child_obj, routing=None)
                    bucket.append(child)
                    self._parse_obj(child_obj, f"{path}.", child)
                continue
            if isinstance(value, dict):
                ft = self.resolve_field(path)
                if ft is not None and (ft.type in GEO_TYPES or ft.type in FEATURE_TYPES
                                       or ft.type in SHAPE_TYPES
                                       or ft.type in RANGE_TYPES
                                       or ft.type in ("join", "percolator",
                                                      "flat_object")):
                    self._index_value(ft, value, parsed)
                else:
                    self._parse_obj(value, f"{path}.", parsed)
                continue
            values = value if isinstance(value, list) else [value]
            if values and all(isinstance(v, dict) for v in values):
                lft = self.resolve_field(path)
                if lft is not None and lft.type in FEATURE_TYPES:
                    raise ValueError(
                        f"[{lft.type}] field [{path}] does not support arrays "
                        f"of feature objects")
                if lft is not None and (lft.type in SHAPE_TYPES
                                        or lft.type in GEO_TYPES
                                        or lft.type in RANGE_TYPES
                                        or lft.type == "flat_object"):
                    for v in values:
                        self._index_value(lft, v, parsed)
                    continue
                for v in values:
                    self._parse_obj(v, f"{path}.", parsed)
                continue
            ft = self.resolve_field(path)
            if ft is None:
                if self.dynamic in (False, "false"):
                    continue
                if self.dynamic == "strict":
                    raise ValueError(f"strict_dynamic_mapping_exception: [{path}] not allowed")
                sample = next((v for v in values if v is not None), None)
                if sample is None:
                    continue
                ft = self._dynamic_type(path, sample)
                if ft is None:
                    continue
                self.fields[path] = ft
            self._index_value(ft, value, parsed)

    def _index_value(self, ft: FieldType, value: Any, parsed: ParsedDocument) -> None:
        if (ft.type in GEO_TYPES and isinstance(value, list) and value
                and isinstance(value[0], numbers.Number)):
            value = [value]  # GeoJSON [lon, lat] is one point, not two values
        if ft.type in VECTOR_TYPES and isinstance(value, list):
            value = [value]  # the whole list is ONE vector value
        values = value if isinstance(value, list) else [value]
        for v in values:
            if v is None:
                v = ft.null_value
                if v is None:
                    continue
            self._index_single(ft, v, parsed)
        for sub in ft.subfields.values():
            self._index_value(sub, value, parsed)
        for target in ft.copy_to:
            tft = self.resolve_field(target)
            if tft is None:
                tft = self._dynamic_type(target, values[0] if values else "")
                if tft is None:
                    continue
                self.fields[target] = tft
            self._index_value(tft, value, parsed)

    def _index_single(self, ft: FieldType, v: Any, parsed: ParsedDocument) -> None:
        name = ft.name
        if ft.store:
            # stored fields keep the raw JSON value (reference StoredField)
            parsed.stored.setdefault(name, []).append(v)
        if ft.type == "percolator":
            # validate the stored query now and extract its pre-filter terms
            # (reference PercolatorFieldMapper + QueryAnalyzer); the query
            # itself lives in _source
            if not isinstance(v, dict):
                raise ValueError(f"percolator field [{name}] must hold a query object")
            from ..search.percolate import extract_index_terms
            from ..search.query_dsl import QueryParseError
            try:
                terms, always = extract_index_terms(v, self)
            except QueryParseError as e:
                raise ValueError(f"percolator query is invalid: {e}")
            if terms:
                parsed.keywords.setdefault(f"{name}#terms", []).extend(terms)
            if always:
                parsed.keywords.setdefault(f"{name}#flags", []).append("any")
            return
        if ft.type == "join":
            # reference ParentJoinFieldMapper: value is the relation name, or
            # {"name": ..., "parent": id} for child docs; children must carry
            # an explicit routing (same-shard requirement for the join)
            if isinstance(v, str):
                rel, parent = v, None
            elif isinstance(v, dict):
                rel, parent = v.get("name"), v.get("parent")
            else:
                raise ValueError(f"cannot parse join field value [{v}]")
            child_rels = {c for cs in ft.relations.values() for c in cs}
            if rel not in set(ft.relations) | child_rels:
                raise ValueError(f"unknown join name [{rel}] for field [{name}]")
            if rel in child_rels:
                if parent is None:
                    raise ValueError(
                        f"[parent] is missing for join field [{name}] "
                        f"child relation [{rel}]")
                if parsed.routing is None:
                    raise ValueError(
                        "[routing] is missing for a doc with a child join "
                        f"relation [{rel}]")
                parsed.terms.setdefault(f"{name}#parent", []).append(str(parent))
                parsed.keywords.setdefault(f"{name}#parent", []).append(str(parent))
            parsed.terms.setdefault(name, []).append(rel)
            parsed.keywords.setdefault(name, []).append(rel)
            return
        if ft.type == "murmur3":
            # plugins/mapper-murmur3 Murmur3FieldMapper: the value itself is
            # not indexed — its murmur3 hash lands in numeric doc values
            # (cardinality-agg fodder). The reference stores the first 64
            # bits of the x64_128 hash; this build uses the same x86_32
            # function the routing layer uses (documented divergence: both
            # are stable murmur3 variants, neither is queryable by value).
            from ..cluster.routing import murmur3_x86_32
            h = murmur3_x86_32(str(v).encode("utf-8"))
            parsed.numerics.setdefault(name, []).append(
                h - 0x100000000 if h >= 0x80000000 else h)
            return
        if ft.type in TEXT_TYPES:
            if ft.index:
                raw_text = str(v)
                annot_spans: list = []
                if ft.type == "annotated_text":
                    # plugins/mapper-annotated-text AnnotatedTextFieldMapper:
                    # inline [text](value1&value2) markup; the plain text is
                    # analyzed normally and each annotation value is injected
                    # as an un-analyzed term at the position of the first
                    # token it covers (phrase positions stay consistent)
                    raw_text, annot_spans = parse_annotated_text(raw_text)
                tokens = self.index_analyzer(ft).analyze(raw_text)
                tl = parsed.terms.setdefault(name, [])
                if ft.type == "match_only_text":
                    # no freqs, no norms, no positions (reference
                    # MatchOnlyTextFieldMapper): tf clamps to 1; phrases
                    # verify against _source at query time
                    seen = set(tl)
                    for t in tokens:
                        if t.text not in seen:
                            tl.append(t.text)
                            seen.add(t.text)
                    return
                pl = parsed.positions.setdefault(name, [])
                # position gap between values; max() not pl[-1] because
                # annotation terms append with the position of the token
                # they cover, which can be far below the value's extent
                base = max(p for _, p in pl) + 100 if pl else 0
                ol = None
                if "offsets" in ft.term_vector:
                    ol = []
                    parsed.offsets.setdefault(name, []).append(ol)
                for t in tokens:
                    tl.append(t.text)
                    pl.append((t.text, base + t.position))
                    if ol is not None:
                        ol.append((t.text, base + t.position,
                                   t.start_offset, t.end_offset))
                for (cs, ce, anns) in annot_spans:
                    # inject each annotation value as an exact term at the
                    # position (and offsets) of the first covered token
                    tok = next((t for t in tokens
                                if cs <= t.start_offset < ce), None)
                    at_pos = base + (tok.position if tok else 0)
                    for a in anns:
                        tl.append(a)
                        pl.append((a, at_pos))
                        if ol is not None and tok is not None:
                            ol.append((a, at_pos, tok.start_offset,
                                       tok.end_offset))
            return
        if ft.type == "binary":
            # base64 payload: stored/_source only, never indexed (reference
            # BinaryFieldMapper)
            return
        if ft.type == "token_count":
            tokens = self.analysis.get(ft.analyzer).analyze(str(v))
            parsed.numerics.setdefault(name, []).append(len(tokens))
            return
        if ft.type == "constant_keyword":
            s = str(v)
            if ft.const_value is None:
                ft.const_value = s     # first value fixes it (reference)
            elif s != ft.const_value:
                raise ValueError(
                    f"[constant_keyword] field [{name}] only accepts value "
                    f"[{ft.const_value}], got [{s}]")
            return                     # indexed for every doc in parse()
        if ft.type == "flat_object":
            # flatten leaves: root field gets every leaf value (searchable
            # + doc values), `name#paths` gets "path=value" terms
            if not isinstance(v, dict):
                raise ValueError(
                    f"[flat_object] field [{name}] must hold an object")
            for sub_path, leaf in _flat_leaves(v, ""):
                s = str(leaf)
                parsed.terms.setdefault(name, []).append(s)
                parsed.keywords.setdefault(name, []).append(s)
                parsed.terms.setdefault(f"{name}#paths", []).append(
                    f"{sub_path}={s}")
                parsed.keywords.setdefault(f"{name}#paths", []).append(
                    f"{sub_path}={s}")
            return
        if ft.type in RANGE_TYPES:
            lo, hi = _parse_range_value(ft, v)
            if lo > hi:
                raise ValueError(
                    f"[{ft.type}] field [{name}]: lower bound [{lo}] > "
                    f"upper bound [{hi}]")
            parsed.numerics.setdefault(f"{name}#lo", []).append(lo)
            parsed.numerics.setdefault(f"{name}#hi", []).append(hi)
            return
        if ft.type in ("keyword", "icu_collation_keyword"):
            s = str(v)
            if ft.ignore_above is not None and len(s) > ft.ignore_above:
                return
            norm = self.index_analyzer(ft).terms(s)
            s = norm[0] if norm else s
            if ft.index:
                parsed.terms.setdefault(name, []).append(s)
            if ft.doc_values:
                parsed.keywords.setdefault(name, []).append(s)
            return
        if ft.type in GEO_TYPES:
            lat, lon = _parse_geo(v)
            parsed.geos.setdefault(name, []).append((lat, lon))
            return
        if ft.type in SHAPE_TYPES:
            from ..search.geo import parse_shape
            # validate now (a bad shape is an index-time 400) and keep the
            # bbox so segment build doesn't re-parse every value
            sh = parse_shape(v)
            parsed.shapes.setdefault(name, []).append((v, sh.bbox))
            return
        if ft.type in FEATURE_TYPES:
            if not isinstance(v, dict):
                raise ValueError(
                    f"[{ft.type}] field [{name}] must hold an object of "
                    f"feature weights")
            bucket = parsed.features.setdefault(name, {})
            for feat, w in v.items():
                w = float(w)
                if w <= 0:
                    raise ValueError(
                        f"[{ft.type}] weights must be positive, got "
                        f"[{feat}]={w}")
                bucket[str(feat)] = w
            return
        if ft.type in VECTOR_TYPES:
            vec = [float(x) for x in (v if isinstance(v, list) else [v])]
            if ft.dims and len(vec) != ft.dims:
                raise ValueError(
                    f"vector length [{len(vec)}] differs from mapped dims "
                    f"[{ft.dims}] for field [{name}]")
            parsed.vectors[name] = vec
            return
        if ft.type == "completion":
            # suggester-only field: lives in _source, served by the host-side
            # prefix index (search/suggest.py completion_suggest)
            return
        cv = coerce_value(ft, v)
        parsed.numerics.setdefault(name, []).append(cv)
        if ft.type == "ip" and ft.index:
            parsed.terms.setdefault(name, []).append(str(v))


def _flat_leaves(obj: dict, prefix: str):
    """Depth-first (path, scalar) leaves of a flat_object value."""
    for k, v in obj.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _flat_leaves(v, f"{path}.")
        elif isinstance(v, list):
            for item in v:
                if isinstance(item, dict):
                    yield from _flat_leaves(item, f"{path}.")
                elif item is not None:
                    yield path, item
        elif v is not None:
            yield path, v


_RANGE_INT_BOUNDS = {
    "integer": (-(1 << 31), (1 << 31) - 1),
    "long": (-(1 << 63), (1 << 63) - 1),
    "date": (-(1 << 63), (1 << 63) - 1),
    "ip": (0, (1 << 63) - 1),
}


def _range_member_coerce(member: str, value: Any, ft: FieldType):
    if member == "date":
        return _parse_date(value, ft.date_format)
    if member == "ip":
        iv = _ip_to_int(str(value))
        if iv >= (1 << 63):
            raise ValueError(
                "ip_range supports IPv4(-mapped) addresses only in this "
                "engine (value exceeds the exact i64 column range)")
        return iv
    if member in ("integer", "long"):
        return int(value)
    return float(value)


def _parse_range_value(ft: FieldType, v: Any) -> Tuple[Any, Any]:
    """{gte/gt/lte/lt} -> closed [lo, hi] in column representation
    (reference RangeType: open bounds nudge by one ulp/step)."""
    import math

    if not isinstance(v, dict):
        raise ValueError(
            f"[{ft.type}] field [{ft.name}] must hold a range object")
    member = RANGE_MEMBER[ft.type]
    is_int = member in _RANGE_INT_BOUNDS
    lo_def, hi_def = (_RANGE_INT_BOUNDS[member] if is_int
                      else (-math.inf, math.inf))
    lo, hi = lo_def, hi_def
    for key, val in v.items():
        if val is None:
            continue
        cv = _range_member_coerce(member, val, ft)
        if key == "gte":
            lo = cv
        elif key == "gt":
            lo = cv + 1 if is_int else float(np_nextafter(cv, math.inf))
        elif key == "lte":
            hi = cv
        elif key == "lt":
            hi = cv - 1 if is_int else float(np_nextafter(cv, -math.inf))
        else:
            raise ValueError(f"unknown range bound [{key}]")
    return lo, hi


def np_nextafter(v, toward):
    import numpy as np
    return np.nextafter(np.float64(v), np.float64(toward))


def _parse_geo(v: Any) -> Tuple[float, float]:
    if isinstance(v, dict):
        return float(v["lat"]), float(v["lon"])
    if isinstance(v, str):
        lat, lon = v.split(",")
        return float(lat), float(lon)
    if isinstance(v, (list, tuple)):  # GeoJSON order [lon, lat]
        return float(v[1]), float(v[0])
    raise ValueError(f"cannot parse geo_point [{v}]")
