"""Per-segment query execution: Query tree -> (scores, mask) device programs.

The analog of the reference's rewrite+createWeight+BulkScorer pipeline
(index/query/*.java building Lucene Queries, executed by QueryPhase
search/query/QueryPhase.java:171) re-shaped for SPMD: every query node
compiles to a dense score vector [n_docs_pad] and a boolean match mask, which
compose on device (bool = masked sums, dis_max = masked max, …). Structural
filters (term/range/exists/ids) build their masks host-side from columnar doc
values — the cacheable "filter context" of the reference — while scoring
clauses (match/knn/sparse) run the ops/ kernels.
"""

from __future__ import annotations

import fnmatch
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from elasticsearch_tpu.index.segment import Segment, next_pow2, BLOCK
from elasticsearch_tpu.mapping import MapperService, parse_date_millis
from elasticsearch_tpu.ops import (
    Bm25Executor, DeviceFeatures, DevicePostings, DeviceVectors, KnnExecutor,
    SparseExecutor, device_live_mask,
)
from elasticsearch_tpu.search import dsl, telemetry
from elasticsearch_tpu.utils.errors import QueryParsingError
from elasticsearch_tpu.mapping.mappers import NUMERIC_TYPES, RANGE_TYPES


@dataclass
class SegmentContext:
    """Execution context for one segment of one shard."""
    segment: Segment
    mappers: MapperService
    segment_idx: int = 0
    # shard- or corpus-wide stats for idf (DFS analog); None = segment-local
    doc_count_override: Optional[int] = None
    df_overrides: Optional[Dict[str, Dict[str, int]]] = None  # field -> term -> df
    # field -> (sum_doc_len, docs_with_field): corpus-wide collection stats
    # (the CollectionStatistics half of DFS) so norms use one global avgdl
    field_stats_overrides: Optional[Dict[str, Tuple[float, int]]] = None
    # point-in-time live mask (a Reader snapshot); when set it REPLACES the
    # segment's current mask so mid-scroll deletes stay invisible
    live_override: Optional[jnp.ndarray] = None
    # the whole shard snapshot this segment belongs to: join queries
    # (has_child/has_parent) must see sibling segments, since parents and
    # children share a shard but not necessarily a segment
    reader: Any = None

    @property
    def n_docs(self) -> int:
        return self.segment.n_docs

    @property
    def n_docs_pad(self) -> int:
        return next_pow2(max(self.segment.n_docs, 1), minimum=BLOCK)

    @property
    def live(self) -> jnp.ndarray:
        if self.live_override is not None:
            return self.live_override
        return device_live_mask(self.segment)

    def to_device_mask(self, host_mask: np.ndarray) -> jnp.ndarray:
        out = np.zeros(self.n_docs_pad, bool)
        out[: len(host_mask)] = host_mask
        return jnp.asarray(out)

    def zeros(self) -> jnp.ndarray:
        return jnp.zeros(self.n_docs_pad, jnp.float32)

    def none_mask(self) -> jnp.ndarray:
        return jnp.zeros(self.n_docs_pad, bool)

    def all_mask(self) -> jnp.ndarray:
        return self.live

    def search_analyzer(self, field_name: str):
        mapper = self.mappers.mapper(field_name)
        if mapper is not None and hasattr(mapper, "search_analyzer"):
            return mapper.search_analyzer
        from elasticsearch_tpu.analysis import STANDARD
        return STANDARD

    def doc_count_for_idf(self) -> int:
        # includes deleted docs, like Lucene stats (df may count tombstones)
        return self.doc_count_override or max(self.segment.n_docs, 1)

    def df_for(self, field_name: str) -> Optional[Dict[str, int]]:
        if self.df_overrides is None:
            return None
        return self.df_overrides.get(field_name)

    def avgdl_for(self, field_name: str) -> Optional[float]:
        """Corpus-wide avgdl for the field, if a DFS coordinator shared it."""
        if self.field_stats_overrides is None:
            return None
        got = self.field_stats_overrides.get(field_name)
        if not got:
            return None
        sum_len, n_docs = got
        if n_docs <= 0:
            return None
        return float(sum_len) / float(n_docs)


Result = Tuple[jnp.ndarray, jnp.ndarray]   # (scores f32 [n_pad], mask bool [n_pad])


def resolve_aliases(q: dsl.Query, mappers: MapperService) -> dsl.Query:
    """Rewrite field aliases to their target paths throughout a query
    tree (FieldAliasMapper resolution, applied once per shard query).
    Generic over the dataclass nodes: any attribute named ``field`` is
    resolved; ``fields`` lists resolve per entry (keeping ^boosts);
    nested Query attributes and lists recurse. Nodes without alias
    references are returned unchanged (no copy)."""
    import dataclasses

    def resolve_name(name: str) -> str:
        base, _, boost = name.partition("^")
        resolved = mappers.resolve_field(base)
        return f"{resolved}^{boost}" if boost else resolved

    if not dataclasses.is_dataclass(q):
        return q
    changes = {}
    for f in dataclasses.fields(q):
        v = getattr(q, f.name)
        if f.name == "field" and isinstance(v, str) and v:
            r = mappers.resolve_field(v)
            if r != v:
                changes[f.name] = r
        elif f.name == "fields" and isinstance(v, list):
            r2 = [resolve_name(x) if isinstance(x, str) else x for x in v]
            if r2 != v:
                changes[f.name] = r2
        elif isinstance(v, dsl.Query):
            r3 = resolve_aliases(v, mappers)
            if r3 is not v:
                changes[f.name] = r3
        elif isinstance(v, list) and v and isinstance(v[0], dsl.Query):
            r4 = [resolve_aliases(c, mappers) for c in v]
            if any(a is not b for a, b in zip(r4, v)):
                changes[f.name] = r4
    if not changes:
        return q
    return dataclasses.replace(q, **changes)


def execute(q: dsl.Query, ctx: SegmentContext) -> Result:
    handler = _HANDLERS.get(type(q))
    if handler is None:
        raise QueryParsingError(f"unsupported query node [{type(q).__name__}]")
    return handler(q, ctx)


# ---------------------------------------------------------------------------
# host-side mask builders (filter context)
# ---------------------------------------------------------------------------

def _term_mask_host(ctx: SegmentContext, field_name: str, value: Any) -> np.ndarray:
    """Docs containing the exact term/value in keyword/numeric/text field."""
    seg = ctx.segment
    n = seg.n_docs
    mask = np.zeros(n, bool)
    if field_name == "_id":
        d = seg.id_to_doc.get(str(value))
        if d is not None:
            mask[d] = True
        return mask
    ftype = ctx.mappers.field_type(field_name)
    if ftype == "constant_keyword":
        # every doc of the index carries the constant — a matching term
        # matches ALL docs, even ones that omitted the field
        const = getattr(ctx.mappers.mapper(field_name), "value", None)
        if const is not None and str(value) == const:
            mask[:] = True
        return mask
    if ftype == "ip":
        import ipaddress
        if "/" in str(value):
            # CIDR term query over the (small) ip term dictionary
            try:
                net = ipaddress.ip_network(str(value), strict=False)
            except ValueError:
                raise QueryParsingError(f"failed to parse CIDR [{value}]")

            def in_net(t: str) -> bool:
                try:
                    return ipaddress.ip_address(t) in net
                except ValueError:
                    return False
            kf_ip = seg.keywords.get(field_name)
            if kf_ip is not None:
                for t in kf_ip.terms:
                    if in_net(t):
                        mask[kf_ip.docs_with_term(t)] = True
            return mask
        # exact: canonicalize the query value like the mapper canonicalized
        # the indexed form ('2001:0DB8::1' must find '2001:db8::1')
        try:
            value = str(ipaddress.ip_address(str(value)))
        except ValueError:
            pass   # non-address text simply matches nothing below
    if ftype is None and "." in field_name:
        # keyed lookup into a flattened field: flat.key -> path-prefixed
        # term on the root column (x-pack FlattenedFieldMapper keyed terms)
        root, _, keypath = field_name.partition(".")
        root_m = ctx.mappers.mapper(root)
        if root_m is not None and root_m.type_name == "flattened":
            from elasticsearch_tpu.mapping.mappers import FLATTENED_SEP
            kf_flat = seg.keywords.get(root)
            if kf_flat is not None:
                mask[kf_flat.docs_with_term(
                    f"{keypath}{FLATTENED_SEP}{value}")] = True
            return mask
    kf = seg.keywords.get(field_name)
    if kf is not None:
        mask[kf.docs_with_term(str(value))] = True
        return mask
    dv = seg.doc_values.get(field_name)
    if dv is not None:
        v = _coerce_numeric(ctx, field_name, value)
        np.equal(dv.values, v, out=mask, where=dv.exists)
        mask &= dv.exists
        # multi-valued docs match if ANY value matches
        for doc, extra in dv.multi.items():
            if not mask[doc] and any(x == v for x in extra):
                mask[doc] = True
        return mask
    pf = seg.postings.get(field_name)
    if pf is not None:
        docs, _ = pf.postings_for(str(value))
        mask[docs] = True
        return mask
    return mask


def _coerce_numeric(ctx: SegmentContext, field_name: str, value: Any) -> float:
    t = ctx.mappers.field_type(field_name)
    try:
        if t == "date":
            return parse_date_millis(value)
        if t == "boolean":
            if isinstance(value, bool):
                return 1.0 if value else 0.0
            return 1.0 if str(value).lower() == "true" else 0.0
        return float(value)
    except (TypeError, ValueError):
        raise QueryParsingError(
            f"failed to parse value [{value}] for field [{field_name}]")


def _range_mask_host(ctx: SegmentContext, q: dsl.Range) -> np.ndarray:
    seg = ctx.segment
    if ctx.mappers.field_type(q.field) == "ip":
        # numeric address-space comparison, not lexicographic strings
        import ipaddress
        mask = np.zeros(seg.n_docs, bool)
        kf = seg.keywords.get(q.field)
        if kf is None:
            return mask

        def addr(v):
            return int(ipaddress.ip_address(str(v)))
        try:
            for term in kf.terms:
                a = addr(term)
                if q.gt is not None and not a > addr(q.gt):
                    continue
                if q.gte is not None and not a >= addr(q.gte):
                    continue
                if q.lt is not None and not a < addr(q.lt):
                    continue
                if q.lte is not None and not a <= addr(q.lte):
                    continue
                mask[kf.docs_with_term(term)] = True
        except ValueError as e:
            raise QueryParsingError(f"failed to parse ip range: {e}")
        return mask
    dv = seg.doc_values.get(q.field)
    if dv is None:
        # range over keyword terms (lexicographic)
        kf = seg.keywords.get(q.field)
        mask = np.zeros(seg.n_docs, bool)
        if kf is None:
            return mask
        for term in kf.terms:
            ok = True
            if q.gt is not None and not term > str(q.gt):
                ok = False
            if q.gte is not None and not term >= str(q.gte):
                ok = False
            if q.lt is not None and not term < str(q.lt):
                ok = False
            if q.lte is not None and not term <= str(q.lte):
                ok = False
            if ok:
                mask[kf.docs_with_term(term)] = True
        return mask
    vals = dv.values
    mask = dv.exists.copy()
    if q.gt is not None:
        mask &= vals > _coerce_numeric(ctx, q.field, q.gt)
    if q.gte is not None:
        mask &= vals >= _coerce_numeric(ctx, q.field, q.gte)
    if q.lt is not None:
        mask &= vals < _coerce_numeric(ctx, q.field, q.lt)
    if q.lte is not None:
        mask &= vals <= _coerce_numeric(ctx, q.field, q.lte)
    # multi-valued docs match if ANY value matches
    for doc, extra in dv.multi.items():
        if mask[doc]:
            continue
        for v in extra:
            ok = True
            if q.gt is not None and not v > _coerce_numeric(ctx, q.field, q.gt):
                ok = False
            if q.gte is not None and not v >= _coerce_numeric(ctx, q.field, q.gte):
                ok = False
            if q.lt is not None and not v < _coerce_numeric(ctx, q.field, q.lt):
                ok = False
            if q.lte is not None and not v <= _coerce_numeric(ctx, q.field, q.lte):
                ok = False
            if ok:
                mask[doc] = True
                break
    return mask


def _exists_mask_host(ctx: SegmentContext, field_name: str) -> np.ndarray:
    seg = ctx.segment
    n = seg.n_docs
    # range fields store nothing under their own name — existence lives
    # on the #lo bound companion column
    if f"{field_name}#lo" in seg.doc_values:
        return seg.doc_values[f"{field_name}#lo"].exists.copy()
    if field_name in seg.doc_values:
        return seg.doc_values[field_name].exists.copy()
    if field_name in seg.keywords:
        kf = seg.keywords[field_name]
        return (np.diff(kf.ord_offsets) > 0)
    if field_name in seg.postings:
        return seg.postings[field_name].doc_lens > 0
    if field_name in seg.vectors:
        return seg.vectors[field_name].exists.copy()
    if field_name in seg.features:
        ff = seg.features[field_name]
        mask = np.zeros(n, bool)
        docs = ff.block_docs.reshape(-1)
        mask[docs[docs >= 0]] = True
        return mask
    if field_name in seg.geo:
        return ~np.isnan(seg.geo[field_name][:, 0])
    return np.zeros(n, bool)


def _expand_terms(ctx: SegmentContext, field_name: str, predicate) -> List[str]:
    """All index terms of a field matching a predicate (prefix/wildcard/regexp/fuzzy)."""
    seg = ctx.segment
    terms: List[str] = []
    kf = seg.keywords.get(field_name)
    if kf is not None:
        terms = [t for t in kf.terms if predicate(t)]
    pf = seg.postings.get(field_name)
    if pf is not None:
        terms += [t for t in pf.terms if predicate(t)]
    return terms


def _multi_term_mask(ctx: SegmentContext, field_name: str, terms: List[str]) -> np.ndarray:
    mask = np.zeros(ctx.segment.n_docs, bool)
    for t in terms:
        mask |= _term_mask_host(ctx, field_name, t)
    return mask


def _cached_filter(ctx: SegmentContext, key, build) -> np.ndarray:
    """Filter cache living on the immutable segment itself, so cached masks
    survive across queries; LRU-bounded like the reference's
    IndicesQueryCache.java:53."""
    return ctx.segment.cached_filter(key, build)


# ---------------------------------------------------------------------------
# handlers
# ---------------------------------------------------------------------------

def _h_match_all(q: dsl.MatchAll, ctx: SegmentContext) -> Result:
    return jnp.full(ctx.n_docs_pad, q.boost, jnp.float32), ctx.all_mask()


def _h_match_none(q: dsl.MatchNone, ctx: SegmentContext) -> Result:
    return ctx.zeros(), ctx.none_mask()


def _bm25_executor(ctx: SegmentContext, field_name: str) -> Optional[Bm25Executor]:
    """Executor cached on the (immutable) segment so its WAND planning
    tables (TermCellIndex / block bounds) survive across queries; the idf
    doc count is refreshed per query since shard-level stats change as
    sibling segments come and go."""
    dev = DevicePostings.for_segment(ctx.segment, field_name)
    if dev is None:
        return None
    ex = ctx.segment.device(
        ("bm25_exec", field_name),
        lambda: Bm25Executor(dev, ctx.segment.postings[field_name]))
    ex.doc_count = ctx.doc_count_for_idf()
    return ex


def _bm25_planner(ctx: SegmentContext, field_name: str
                  ) -> Optional[Bm25Executor]:
    """Host-side executor for PLAN BUILDING only (the plane path): no
    per-segment device mirror is uploaded or breaker-charged — the plane
    already holds the shard's postings on device, and doubling residency
    with mirrors the plane never dispatches would tighten the very budget
    the registry manages. Reuses a full executor when one is already
    cached (its host planning tables are identical)."""
    cached = ctx.segment._device_cache.get(("bm25_exec", field_name))
    if cached is not None:
        cached.doc_count = ctx.doc_count_for_idf()
        return cached
    pf = ctx.segment.postings.get(field_name)
    if pf is None:
        return None
    ex = ctx.segment.device(
        ("bm25_plan", field_name),
        lambda: Bm25Executor(None, pf,
                             total_doc_count=max(ctx.segment.n_docs, 1)))
    ex.doc_count = ctx.doc_count_for_idf()
    return ex


def _h_match(q: dsl.Match, ctx: SegmentContext) -> Result:
    analyzer = ctx.search_analyzer(q.field)
    terms = analyzer.terms(q.text)
    if not terms:
        return ctx.zeros(), ctx.none_mask()
    ex = _bm25_executor(ctx, q.field)
    if ex is None:
        # not a text field: fall back to term-equality semantics
        return _h_term(dsl.Term(field=q.field, value=q.text, boost=q.boost), ctx)
    scores = ex.scores(terms, ctx.live, boost=q.boost,
                       df_override=ctx.df_for(q.field),
                       avgdl_override=ctx.avgdl_for(q.field))
    mask = scores > 0.0
    msm = dsl.resolve_minimum_should_match(q.minimum_should_match, len(set(terms)))
    if q.operator == "and" or msm > 1:
        need = len(terms) if q.operator == "and" else msm
        count = np.zeros(ctx.segment.n_docs, np.int32)
        pf = ctx.segment.postings[q.field]
        for t in set(terms):
            docs, _ = pf.postings_for(t)
            count[docs] += 1
        mask = mask & ctx.to_device_mask(count >= min(need, len(set(terms))))
    return jnp.where(mask, scores, 0.0), mask


def _h_multi_match(q: dsl.MultiMatch, ctx: SegmentContext) -> Result:
    results = []
    expanded: dict = {}   # field -> boost; dedup keeps the highest boost
    for f in q.fields:
        fname, _, fboost = f.partition("^")
        boost = q.boost * (float(fboost) if fboost else 1.0)
        if "*" in fname:
            # wildcard field patterns expand to matching text-ish fields
            # (QueryParserHelper.resolveMappingFields analog; resolved
            # fields are DEDUPED so most_fields never double-counts)
            for name in ctx.mappers.field_names():
                if fnmatch.fnmatch(name, fname) and \
                        ctx.mappers.field_type(name) in (
                            "text", "keyword", "search_as_you_type"):
                    expanded[name] = max(expanded.get(name, 0.0), boost)
        else:
            expanded[fname] = max(expanded.get(fname, 0.0), boost)
    for fname, boost in expanded.items():
        if q.type == "bool_prefix":
            # search-as-you-type: every term matches normally, the LAST
            # term matches as a prefix, joined by the OPERATOR (default
            # OR — any clause suffices; "and" requires all), per
            # MultiMatchQueryBuilder Type.BOOL_PREFIX /
            # MatchBoolPrefixQueryBuilder
            toks = ctx.search_analyzer(fname).terms(q.text)
            if not toks:
                continue
            head = " ".join(toks[:-1])
            clauses: List[dsl.Query] = []
            if head:
                clauses.append(dsl.Match(field=fname, text=head,
                                         operator=q.operator))
            clauses.append(dsl.Prefix(field=fname, value=toks[-1]))
            if q.operator == "and":
                node = dsl.Bool(must=clauses, boost=boost)
            else:
                node = dsl.Bool(should=clauses,
                                minimum_should_match=1, boost=boost)
            results.append(execute(node, ctx))
            continue
        results.append(execute(dsl.Match(field=fname, text=q.text,
                                         operator=q.operator, boost=boost), ctx))
    if not results:
        return ctx.zeros(), ctx.none_mask()
    scores = jnp.stack([r[0] for r in results])
    masks = jnp.stack([r[1] for r in results])
    any_mask = jnp.any(masks, axis=0)
    if q.type == "most_fields":
        total = jnp.sum(scores, axis=0)
    else:  # best_fields
        total = jnp.max(scores, axis=0)
    return jnp.where(any_mask, total, 0.0), any_mask


def _h_match_phrase(q: dsl.MatchPhrase, ctx: SegmentContext) -> Result:
    analyzer = ctx.search_analyzer(q.field)
    tokens = analyzer.analyze(q.text)
    if not tokens:
        return ctx.zeros(), ctx.none_mask()
    pf = ctx.segment.postings.get(q.field)
    if pf is None:
        return ctx.zeros(), ctx.none_mask()
    # candidates: docs containing all terms (host AND of postings)
    cand: Optional[np.ndarray] = None
    for tok in tokens:
        docs, _ = pf.postings_for(tok.term)
        s = set(docs.tolist())
        cand = s if cand is None else (cand & s)
        if not cand:
            break
    matched = []
    if cand:
        # verify positions host-side (fetch-sized candidate sets)
        rel = [t.position - tokens[0].position for t in tokens]
        for doc in cand:
            first = pf.positions_for(tokens[0].term, doc)
            ok = False
            for p0 in first:
                if all(_has_position(pf, t.term, doc, p0 + r, q.slop)
                       for t, r in zip(tokens[1:], rel[1:])):
                    ok = True
                    break
            if ok:
                matched.append(doc)
    mask_host = np.zeros(ctx.segment.n_docs, bool)
    mask_host[matched] = True
    mask = ctx.to_device_mask(mask_host) & ctx.live
    # score matched docs with the BM25 of the phrase terms (documented
    # divergence: the reference scores by phrase frequency)
    ex = _bm25_executor(ctx, q.field)
    scores = ex.scores([t.term for t in tokens], ctx.live, boost=q.boost,
                       df_override=ctx.df_for(q.field),
                       avgdl_override=ctx.avgdl_for(q.field))
    return jnp.where(mask, scores, 0.0), mask


def _h_match_phrase_prefix(q: dsl.MatchPhrasePrefix,
                           ctx: SegmentContext) -> Result:
    """Phrase match with the last term prefix-expanded against the term
    dictionary (MatchPhrasePrefixQueryBuilder's MultiPhrasePrefixQuery,
    capped at max_expansions)."""
    analyzer = ctx.search_analyzer(q.field)
    tokens = analyzer.analyze(q.text)
    if not tokens:
        return ctx.zeros(), ctx.none_mask()
    pf = ctx.segment.postings.get(q.field)
    if pf is None:
        return ctx.zeros(), ctx.none_mask()
    prefix = tokens[-1].term
    expansions = sorted(t for t in pf.terms
                        if t.startswith(prefix))[: q.max_expansions]
    if not expansions:
        return ctx.zeros(), ctx.none_mask()
    head = tokens[:-1]
    # candidates: docs with all head terms AND any expansion
    cand: Optional[set] = None
    for tok in head:
        docs, _ = pf.postings_for(tok.term)
        s = set(docs.tolist())
        cand = s if cand is None else (cand & s)
        if not cand:
            break
    exp_docs: set = set()
    for term in expansions:
        docs, _ = pf.postings_for(term)
        exp_docs.update(docs.tolist())
    cand = exp_docs if cand is None else (cand & exp_docs)
    matched = []
    rel = [t.position - tokens[0].position for t in tokens]
    for doc in cand or ():
        starts = (pf.positions_for(head[0].term, doc)
                  if head else pf.positions_for(expansions[0], doc))
        ok = False
        if not head:
            ok = True   # single prefix term: presence is a match
        else:
            for p0 in starts:
                if all(_has_position(pf, t.term, doc, p0 + r, 0)
                       for t, r in zip(head[1:], rel[1:-1])):
                    if any(_has_position(pf, e, doc, p0 + rel[-1], 0)
                           for e in expansions):
                        ok = True
                        break
        if ok:
            matched.append(doc)
    mask_host = np.zeros(ctx.segment.n_docs, bool)
    mask_host[matched] = True
    mask = ctx.to_device_mask(mask_host) & ctx.live
    # score matched docs with BM25 over the head terms + expansions, the
    # same analog _h_match_phrase documents (constant scoring would rank
    # many-occurrence docs identically to one-occurrence docs)
    ex = _bm25_executor(ctx, q.field)
    score_terms = [t.term for t in head] + expansions
    scores = ex.scores(score_terms, ctx.live, boost=q.boost,
                       df_override=ctx.df_for(q.field),
                       avgdl_override=ctx.avgdl_for(q.field))
    return jnp.where(mask, scores, 0.0), mask


def _h_more_like_this(q: dsl.MoreLikeThis, ctx: SegmentContext) -> Result:
    """Top tf-idf terms from the like-texts scored as a bag of shoulds
    (MoreLikeThisQueryBuilder's term selection, per field)."""
    from collections import Counter
    total_scores = None
    any_mask = None
    fields = q.fields or [
        name for name in ctx.mappers.field_names()
        if ctx.mappers.field_type(name) == "text"]
    for fname in fields:
        pf = ctx.segment.postings.get(fname)
        ex = _bm25_executor(ctx, fname)
        if pf is None or ex is None:
            continue
        analyzer = ctx.search_analyzer(fname)
        tf = Counter(t for text in q.like for t in analyzer.terms(text))
        doc_count = ctx.doc_count_for_idf()
        scored = []
        for term, freq in tf.items():
            if freq < q.min_term_freq:
                continue
            tid = pf.terms.get(term)
            df = int(pf.doc_freq[tid]) if tid is not None else 0
            if df < q.min_doc_freq:
                continue
            idf = np.log(1.0 + (doc_count - df + 0.5) / (df + 0.5))
            scored.append((freq * idf, term))
        scored.sort(reverse=True)
        terms = [t for _s, t in scored[: q.max_query_terms]]
        if not terms:
            continue
        scores = ex.scores(terms, ctx.live, boost=q.boost,
                           df_override=ctx.df_for(fname),
                           avgdl_override=ctx.avgdl_for(fname))
        mask = scores > 0.0
        total_scores = scores if total_scores is None \
            else total_scores + scores
        any_mask = mask if any_mask is None else (any_mask | mask)
    if total_scores is None:
        return ctx.zeros(), ctx.none_mask()
    return jnp.where(any_mask, total_scores, 0.0), any_mask


EARTH_RADIUS_M = 6_371_000.0


def _geo_column(ctx: SegmentContext, field_name: str) -> np.ndarray:
    arr = ctx.segment.geo.get(field_name)
    if arr is None:
        return np.full((ctx.segment.n_docs, 2), np.nan)
    return arr


def _h_geo_distance(q: dsl.GeoDistance, ctx: SegmentContext) -> Result:
    def build():
        pts = _geo_column(ctx, q.field)
        lat = np.radians(pts[:, 0])
        lon = np.radians(pts[:, 1])
        qlat, qlon = np.radians(q.lat), np.radians(q.lon)
        # haversine (GeoDistance.ARC)
        a = np.sin((lat - qlat) / 2) ** 2 + \
            np.cos(lat) * np.cos(qlat) * np.sin((lon - qlon) / 2) ** 2
        d = 2 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0, 1)))
        mask = np.nan_to_num(d, nan=np.inf) <= q.distance_m
        return ctx.to_device_mask(mask)
    mask = ctx.segment.cached_filter(
        ("geo_distance", q.field, q.lat, q.lon, q.distance_m), build) \
        & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_geo_bounding_box(q: dsl.GeoBoundingBox, ctx: SegmentContext) -> Result:
    def build():
        pts = _geo_column(ctx, q.field)
        lat, lon = pts[:, 0], pts[:, 1]
        # NaN (missing field) compares False on both sides: excluded
        in_lat = (lat <= q.top) & (lat >= q.bottom)
        if q.left <= q.right:
            in_lon = (lon >= q.left) & (lon <= q.right)
        else:   # box crossing the antimeridian
            in_lon = (lon >= q.left) | (lon <= q.right)
        return ctx.to_device_mask(in_lat & in_lon)
    mask = ctx.segment.cached_filter(
        ("geo_bbox", q.field, q.top, q.left, q.bottom, q.right), build) \
        & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _has_position(pf, term: str, doc: int, want: int, slop: int) -> bool:
    pos = pf.positions_for(term, doc)
    if slop == 0:
        return bool(np.any(pos == want))
    return bool(np.any(np.abs(pos - want) <= slop))


def _h_term(q: dsl.Term, ctx: SegmentContext) -> Result:
    key = ("term", q.field, str(q.value))
    mask_host = _cached_filter(ctx, key, lambda: _term_mask_host(ctx, q.field, q.value))
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_terms(q: dsl.Terms, ctx: SegmentContext) -> Result:
    key = ("terms", q.field, tuple(str(v) for v in q.values))
    mask_host = _cached_filter(
        ctx, key, lambda: np.logical_or.reduce(
            [_term_mask_host(ctx, q.field, v) for v in q.values])
        if q.values else np.zeros(ctx.segment.n_docs, bool))
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _range_field_mask(ctx: SegmentContext, q: dsl.Range,
                      mapper) -> np.ndarray:
    """Interval relations against a RANGE field: the doc's [lo, hi]
    bounds live on the #lo/#hi companion columns
    (RangeFieldMapper.RangeType query semantics)."""
    seg = ctx.segment
    coerce = mapper._coerce
    qlo = coerce(q.gte) if q.gte is not None else (
        coerce(q.gt) if q.gt is not None else -np.inf)
    qhi = coerce(q.lte) if q.lte is not None else (
        coerce(q.lt) if q.lt is not None else np.inf)
    lo_dv = seg.doc_values.get(f"{q.field}#lo")
    hi_dv = seg.doc_values.get(f"{q.field}#hi")
    if lo_dv is None or hi_dv is None:
        return np.zeros(seg.n_docs, bool)

    def relate(lo, hi) -> bool:
        if q.relation == "within":
            return lo >= qlo and hi <= qhi
        if q.relation == "contains":
            return lo <= qlo and hi >= qhi
        return lo <= qhi and hi >= qlo   # intersects

    lo = lo_dv.values.astype(np.float64)
    hi = hi_dv.values.astype(np.float64)
    exists = lo_dv.exists & hi_dv.exists
    if q.relation == "within":
        rel = (lo >= qlo) & (hi <= qhi)
    elif q.relation == "contains":
        rel = (lo <= qlo) & (hi >= qhi)
    else:   # intersects
        rel = (lo <= qhi) & (hi >= qlo)
    mask = exists & rel
    # multi-valued docs: ANY of the doc's ranges may satisfy the relation
    # (lo.multi[d][i] pairs with hi.multi[d][i])
    for d in set(lo_dv.multi) | set(hi_dv.multi):
        los = lo_dv.multi.get(d, [lo[d]])
        his = hi_dv.multi.get(d, [hi[d]])
        mask[d] = any(relate(float(a), float(b))
                      for a, b in zip(los, his))
    return mask


def _h_range(q: dsl.Range, ctx: SegmentContext) -> Result:
    mapper = ctx.mappers.mapper(q.field)
    if mapper is not None and \
            getattr(mapper, "type_name", "") in RANGE_TYPES:
        key = ("range_field", q.field, str(q.gt), str(q.gte), str(q.lt),
               str(q.lte), q.relation)
        mask_host = _cached_filter(
            ctx, key, lambda: _range_field_mask(ctx, q, mapper))
        mask = ctx.to_device_mask(mask_host) & ctx.live
        return jnp.where(mask, jnp.float32(q.boost), 0.0), mask
    key = ("range", q.field, str(q.gt), str(q.gte), str(q.lt), str(q.lte))
    mask_host = _cached_filter(ctx, key, lambda: _range_mask_host(ctx, q))
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_exists(q: dsl.Exists, ctx: SegmentContext) -> Result:
    mask_host = _cached_filter(ctx, ("exists", q.field),
                               lambda: _exists_mask_host(ctx, q.field))
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_ids(q: dsl.Ids, ctx: SegmentContext) -> Result:
    mask_host = np.zeros(ctx.segment.n_docs, bool)
    for doc_id in q.values:
        d = ctx.segment.id_to_doc.get(doc_id)
        if d is not None:
            mask_host[d] = True
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_prefix(q: dsl.Prefix, ctx: SegmentContext) -> Result:
    terms = _expand_terms(ctx, q.field, lambda t: t.startswith(q.value))
    mask = ctx.to_device_mask(_multi_term_mask(ctx, q.field, terms)) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_wildcard(q: dsl.Wildcard, ctx: SegmentContext) -> Result:
    rx = re.compile(fnmatch.translate(q.value))
    terms = _expand_terms(ctx, q.field, lambda t: rx.match(t) is not None)
    mask = ctx.to_device_mask(_multi_term_mask(ctx, q.field, terms)) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_regexp(q: dsl.Regexp, ctx: SegmentContext) -> Result:
    rx = re.compile(q.value)
    terms = _expand_terms(ctx, q.field, lambda t: rx.fullmatch(t) is not None)
    mask = ctx.to_device_mask(_multi_term_mask(ctx, q.field, terms)) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_fuzzy(q: dsl.Fuzzy, ctx: SegmentContext) -> Result:
    max_edits = _fuzziness_to_edits(q.fuzziness, q.value)
    terms = _expand_terms(
        ctx, q.field, lambda t: _levenshtein_within(t, q.value, max_edits))
    mask = ctx.to_device_mask(_multi_term_mask(ctx, q.field, terms)) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _fuzziness_to_edits(fuzziness: Any, value: str) -> int:
    if isinstance(fuzziness, int):
        return fuzziness
    s = str(fuzziness).upper()
    if s == "AUTO":
        n = len(value)
        return 0 if n <= 2 else (1 if n <= 5 else 2)
    return int(s)


def _levenshtein_within(a: str, b: str, k: int) -> bool:
    if abs(len(a) - len(b)) > k:
        return False
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb)))
        if min(cur) > k:
            return False
        prev = cur
    return prev[-1] <= k


def _h_bool(q: dsl.Bool, ctx: SegmentContext) -> Result:
    scores = ctx.zeros()
    mask = None

    for clause in q.must:
        s, m = execute(clause, ctx)
        scores = scores + s
        mask = m if mask is None else (mask & m)
    for clause in q.filter:
        _, m = execute(clause, ctx)
        mask = m if mask is None else (mask & m)

    if q.should:
        should_scores = ctx.zeros()
        should_count = jnp.zeros(ctx.n_docs_pad, jnp.int32)
        for clause in q.should:
            s, m = execute(clause, ctx)
            should_scores = should_scores + jnp.where(m, s, 0.0)
            should_count = should_count + m.astype(jnp.int32)
        if q.minimum_should_match is None:
            # should is optional when must/filter exist; required otherwise
            msm = 0 if (q.must or q.filter) else 1
        else:
            msm = dsl.resolve_minimum_should_match(
                q.minimum_should_match, len(q.should))
        if msm > 0:
            should_mask = should_count >= msm
            mask = should_mask if mask is None else (mask & should_mask)
        scores = scores + should_scores

    if mask is None:
        mask = ctx.all_mask()
    for clause in q.must_not:
        _, m = execute(clause, ctx)
        mask = mask & ~m

    mask = mask & ctx.live
    return jnp.where(mask, scores * q.boost, 0.0), mask


def _h_constant_score(q: dsl.ConstantScore, ctx: SegmentContext) -> Result:
    _, mask = execute(q.filter, ctx)
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_dis_max(q: dsl.DisMax, ctx: SegmentContext) -> Result:
    if not q.queries:
        return ctx.zeros(), ctx.none_mask()
    results = [execute(c, ctx) for c in q.queries]
    scores = jnp.stack([r[0] for r in results])
    masks = jnp.stack([r[1] for r in results])
    best = jnp.max(scores, axis=0)
    rest = jnp.sum(scores, axis=0) - best
    total = best + q.tie_breaker * rest
    mask = jnp.any(masks, axis=0)
    return jnp.where(mask, total * q.boost, 0.0), mask


def _h_boosting(q: dsl.Boosting, ctx: SegmentContext) -> Result:
    pos_s, pos_m = execute(q.positive, ctx)
    _, neg_m = execute(q.negative, ctx)
    scores = jnp.where(neg_m, pos_s * q.negative_boost, pos_s)
    return jnp.where(pos_m, scores, 0.0), pos_m


@dataclass
class KnnBound(dsl.Query):
    """A Knn node rewritten to its shard-global top-k doc set.

    Mirrors Lucene's KnnVectorQuery rewrite: per-leaf top-k, merged to a
    global k, then executed as an exact doc-id/score set. Built by
    rewrite_knn() in the shard query phase."""
    per_segment: Dict[int, Tuple[np.ndarray, np.ndarray]] = None  # si -> (docs, scores)
    boost: float = 1.0


# segments at or above this size use the IVF ANN path by default (below it
# exact brute force is both faster and perfectly accurate)
ANN_DEFAULT_MIN_DOCS = 65536


def ann_segment_route(ctx: "SegmentContext", field: str, k: int,
                      num_candidates: int, filtered: bool = False
                      ) -> Optional[Tuple]:
    """IVF routing decision for one segment, shared by the solo kNN
    rewrite and the batched executor so they cannot diverge.

    None = take the exact brute-force path (small segment, filtered
    query, unknown index type, or no vector column). Otherwise
    (index, rows, oversample, nprobe) — with index None when the field
    is mapped but this segment holds zero vectors (no hits)."""
    if filtered:
        return None       # filtered kNN stays exact (correctness first)
    seg = ctx.segment
    vf = seg.vectors.get(field)
    if vf is None:
        return None
    mapper = ctx.mappers.mapper(field)
    opts = getattr(mapper, "index_options", None) or {}
    wants_ivf = opts.get("type") == "ivf"
    if not wants_ivf and seg.n_docs < ANN_DEFAULT_MIN_DOCS:
        return None
    if opts.get("type") not in (None, "ivf"):
        return None       # unknown index type: exact
    from elasticsearch_tpu.ops.ivf import IVFIndex

    def build():
        rows = np.nonzero(vf.exists)[0]
        if len(rows) == 0:
            return None, rows.astype(np.int64)
        index = IVFIndex.build(vf.matrix[rows],
                               nlist=opts.get("nlist"),
                               similarity=vf.similarity)
        return index, rows.astype(np.int64)
    from elasticsearch_tpu.indices.breaker import BREAKERS
    from elasticsearch_tpu.utils.errors import CircuitBreakingError
    # budget refusals memoize under the breaker limit they were refused
    # at (the plane registry's budget-token pattern): no re-paying the
    # full k-means per query just to re-trip, but a raised limit retries
    budget_token = BREAKERS.breaker("device").limit
    if seg._device_cache.get(("ivf_refused", field)) == budget_token:
        return None
    try:
        index, rows = seg.device(("ivf", field), build)
    except CircuitBreakingError:
        seg._device_cache[("ivf_refused", field)] = budget_token
        return None       # index over HBM budget: exact brute force serves
    if index is None:
        return (None, rows, 0, 0)   # mapped, but no vectors here

    oversample = min(max(2 * k, k + 16), len(rows))
    nprobe = opts.get("nprobe") or max(
        1, int(np.ceil(num_candidates / max(index.list_len, 1))))
    return (index, rows, oversample, nprobe)


def filter_context_mask(ctx: "SegmentContext", filt, filter_key,
                        stats: Optional[Dict[str, float]] = None
                        ) -> np.ndarray:
    """Host-side filter-context mask [n_docs_pad] for one segment,
    cached ACROSS drains on the immutable segment keyed by the filter's
    value key (the reader-generation component of the cache key IS the
    segment identity: a refresh produces new segments, deletes ride the
    live mask — never the match mask). The batched kNN paths used to
    re-execute every distinct filter tree per drain before stacking the
    [Q, N_pad] masks; now only the stack itself rebuilds."""
    def build():
        _, fmask = execute(filt, ctx)
        return np.asarray(fmask)
    if filter_key is None:
        return build()
    key = ("filter_ctx_mask", filter_key)
    hit = key in ctx.segment._filter_cache
    mask = _cached_filter(ctx, key, build)
    if stats is not None and hit:
        stats["filter_mask_reuses"] = stats.get("filter_mask_reuses",
                                                0) + 1
    return mask


def knn_shard_winners(ctxs: List["SegmentContext"], field: str, specs,
                      k: int, check_members=None,
                      stats: Optional[Dict[str, float]] = None
                      ) -> List[List[Tuple[int, int, float]]]:
    """THE kNN top-k executor for the served path — Q queries (each a
    spec with query_vector / filter / filter_key / num_candidates),
    solo being simply Q=1. Returns one sorted ``[(segment_idx,
    local_doc, raw_score)]`` winner list (len <= k) per member, the
    merge Lucene's KnnVectorQuery rewrite performs.

    Route per segment and member, one shared dispatch per class:
    the resident whole-shard plane (one matmul / one shard-IVF probe via
    plane_exec.plane_knn_winners), else per segment: unfiltered members
    on IVF-routed segments share one batched nprobe probe per DERIVED
    PROBE WIDTH (members whose num_candidates imply different nprobe
    probe in separate groups — each exactly as it would alone — instead
    of falling anywhere back), filtered members one (optionally masked)
    [Q, D] x [D, N] matmul with filter-context masks computed once per
    distinct filter (cached across drains per segment)."""
    n_q = len(specs)
    per_member_hits: List[List[Tuple[int, int, float]]] = \
        [[] for _ in range(n_q)]
    if ctxs:
        from elasticsearch_tpu.ops.device_segment import PLANES
        part = PLANES.get([c.segment for c in ctxs], "vectors", field)
        if part is not None:
            from elasticsearch_tpu.search.plane_exec import (
                plane_knn_winners,
            )
            return plane_knn_winners(ctxs, part, field, specs, k,
                                     check_members, stats)
    vectors = np.asarray([s.query_vector for s in specs], np.float32)
    unfiltered = [qi for qi in range(n_q) if specs[qi].filter is None]
    for ctx in ctxs:
        dev = DeviceVectors.for_segment(ctx.segment, field)
        if dev is None:
            continue
        if check_members is not None:
            check_members()
        exact_idx = list(range(n_q))
        if unfiltered and ann_segment_route(
                ctx, field, k, specs[unfiltered[0]].num_candidates,
                filtered=False) is not None:
            # IVF-routed segment: group unfiltered members by the probe
            # width their num_candidates implies; each group probes in
            # one batched dispatch, exactly as its members would solo
            groups: Dict[int, Tuple[Tuple, List[int]]] = {}
            for qi in unfiltered:
                route = ann_segment_route(
                    ctx, field, k, specs[qi].num_candidates,
                    filtered=False)
                groups.setdefault(route[3], (route, []))[1].append(qi)
            live_host = np.asarray(ctx.live)[: ctx.segment.n_docs]
            for nprobe, (route, members) in sorted(groups.items()):
                index, rows, oversample, _n = route
                if index is None:
                    continue     # mapped, but no vectors here
                probed = index.probe_live(
                    vectors[members], k, nprobe, rows, live_host,
                    ctx.segment_idx, oversample)
                for qi, hits in zip(members, probed):
                    per_member_hits[qi].extend(hits)
            exact_idx = [qi for qi in range(n_q)
                         if specs[qi].filter is not None]
        if not exact_idx:
            continue
        # exact path: distinct filters resolve to masks once per segment
        # (cached across drains on the segment itself)
        masks = None
        fkeys = {specs[qi].filter_key for qi in exact_idx}
        if fkeys != {None}:
            by_key: Dict[Optional[str], Any] = {}
            for qi in exact_idx:
                s_qi = specs[qi]
                if s_qi.filter is not None and \
                        s_qi.filter_key not in by_key:
                    by_key[s_qi.filter_key] = filter_context_mask(
                        ctx, s_qi.filter, s_qi.filter_key, stats)
            if len(fkeys) == 1:
                # every member carries the SAME filter: one shared mask
                masks = jnp.asarray(by_key[next(iter(fkeys))])
                if stats is not None:
                    stats["knn_shared_mask_segments"] = \
                        stats.get("knn_shared_mask_segments", 0) + 1
            else:
                rows_m = np.ones((len(exact_idx), ctx.n_docs_pad), bool)
                for row, qi in enumerate(exact_idx):
                    fk = specs[qi].filter_key
                    if fk is not None:
                        rows_m[row] = by_key[fk]
                masks = rows_m
        ex = KnnExecutor(dev)
        k_seg = min(k, ctx.n_docs_pad)
        s, d = ex.top_k_batch(vectors[exact_idx], ctx.live, k_seg, masks)
        s, d = telemetry.to_host(s, d)
        for row, qi in enumerate(exact_idx):
            for sc, doc in zip(s[row], d[row]):
                if sc > -np.inf:
                    per_member_hits[qi].append(
                        (ctx.segment_idx, int(doc), float(sc)))
    for qi in range(n_q):
        per_member_hits[qi].sort(key=lambda x: -x[2])
        per_member_hits[qi] = per_member_hits[qi][:k]
    return per_member_hits


def rewrite_knn(q: dsl.Query, segment_ctxs: List["SegmentContext"],
                cancel_check=None) -> dsl.Query:
    """Replace every Knn node with a KnnBound node holding the shard-global
    top-k (merged across segments). ``cancel_check`` (zero-arg, raising)
    runs between per-segment device dispatches so a cancelled or
    budget-expired task stops paying for vector scans.

    The rewrite IS a batch of one: it calls ``knn_shard_winners`` — the
    same executor the micro-batcher's kNN drains run — with a single
    spec, so solo and batched kNN cannot diverge (one kernel call-site
    per route: plane matmul / shard-IVF probe / per-segment matmul /
    per-segment grouped probe)."""
    if isinstance(q, dsl.Knn):
        from types import SimpleNamespace
        spec = SimpleNamespace(
            query_vector=q.query_vector, filter=q.filter,
            filter_key=repr(q.filter) if q.filter is not None else None,
            num_candidates=q.num_candidates)
        winners = knn_shard_winners(segment_ctxs, q.field, [spec], q.k,
                                    check_members=cancel_check)[0]
        per_segment: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for si, d, s in winners:
            docs, scores = per_segment.setdefault(
                si, ([], []))  # type: ignore[assignment]
            docs.append(d)
            scores.append(s * q.boost)
        per_segment = {si: (np.asarray(d, np.int64), np.asarray(s, np.float32))
                       for si, (d, s) in per_segment.items()}
        return KnnBound(per_segment=per_segment, boost=q.boost)
    # recurse into compound nodes
    if isinstance(q, dsl.Bool):
        return dsl.Bool(must=[rewrite_knn(c, segment_ctxs, cancel_check)
                              for c in q.must],
                        should=[rewrite_knn(c, segment_ctxs, cancel_check)
                                for c in q.should],
                        must_not=[rewrite_knn(c, segment_ctxs, cancel_check)
                                  for c in q.must_not],
                        filter=[rewrite_knn(c, segment_ctxs, cancel_check)
                                for c in q.filter],
                        minimum_should_match=q.minimum_should_match, boost=q.boost)
    if isinstance(q, dsl.DisMax):
        return dsl.DisMax(queries=[rewrite_knn(c, segment_ctxs, cancel_check)
                                   for c in q.queries],
                          tie_breaker=q.tie_breaker, boost=q.boost)
    if isinstance(q, dsl.ConstantScore) and q.filter is not None:
        return dsl.ConstantScore(
            filter=rewrite_knn(q.filter, segment_ctxs, cancel_check),
            boost=q.boost)
    if isinstance(q, dsl.Boosting):
        return dsl.Boosting(
            positive=rewrite_knn(q.positive, segment_ctxs, cancel_check),
            negative=rewrite_knn(q.negative, segment_ctxs, cancel_check),
            negative_boost=q.negative_boost, boost=q.boost)
    if isinstance(q, dsl.ScriptScore) and q.query is not None:
        return dsl.ScriptScore(
            query=rewrite_knn(q.query, segment_ctxs, cancel_check),
            source=q.source, params=q.params, boost=q.boost)
    if isinstance(q, dsl.FunctionScore) and q.query is not None:
        return dsl.FunctionScore(
            query=rewrite_knn(q.query, segment_ctxs, cancel_check),
            functions=q.functions, boost_mode=q.boost_mode,
            score_mode=q.score_mode, boost=q.boost)
    return q


def _h_knn_bound(q: KnnBound, ctx: SegmentContext) -> Result:
    entry = (q.per_segment or {}).get(ctx.segment_idx)
    if entry is None:
        return ctx.zeros(), ctx.none_mask()
    docs, doc_scores = entry
    scores_host = np.zeros(ctx.n_docs_pad, np.float32)
    mask_host = np.zeros(ctx.n_docs_pad, bool)
    scores_host[docs] = doc_scores
    mask_host[docs] = True
    return jnp.asarray(scores_host), jnp.asarray(mask_host)


def _h_knn(q: dsl.Knn, ctx: SegmentContext) -> Result:
    """Direct (single-segment) execution; the shard phase normally rewrites
    Knn to KnnBound first for shard-global k semantics."""
    bound = rewrite_knn(q, [ctx])
    return _h_knn_bound(bound, ctx)


def _h_rank_feature(q: dsl.RankFeature, ctx: SegmentContext) -> Result:
    # rank_feature targets a single feature inside a rank_features field, or a
    # standalone rank_feature field (stored as a single-feature field).
    fname, _, feat = q.field.partition(".")
    if feat and fname in ctx.segment.features:
        field_name, feature = fname, feat
    elif q.field in ctx.segment.features:
        field_name, feature = q.field, q.field
    else:
        return ctx.zeros(), ctx.none_mask()
    dev = DeviceFeatures.for_segment(ctx.segment, field_name)
    ex = SparseExecutor(dev, ctx.segment.features[field_name])
    pivot = q.scaling_factor if q.function == "log" else q.pivot
    scores = ex.scores([(feature, q.boost)], ctx.live,
                       function=q.function, pivot=pivot, exponent=q.exponent)
    return scores, scores > 0.0


def _h_text_expansion(q: dsl.TextExpansion, ctx: SegmentContext) -> Result:
    dev = DeviceFeatures.for_segment(ctx.segment, q.field)
    if dev is None:
        return ctx.zeros(), ctx.none_mask()
    tokens = q.tokens
    if tokens is None:
        # raw query text: run the expansion model on device at query time
        # (the x-pack inference rewrite, NativeController.java:29 analog,
        # collapsed into a local jitted dispatch)
        from elasticsearch_tpu.ml import get_model
        tokens = get_model(q.model_id).expand(q.model_text or "")
    ex = SparseExecutor(dev, ctx.segment.features[q.field])
    scores = ex.scores([(t, w * q.boost) for t, w in tokens.items()],
                       ctx.live, function="linear")
    return scores, scores > 0.0


def _join_field(ctx: SegmentContext) -> Optional[str]:
    """The index's single join field name, from the mappers."""
    for name in ctx.mappers.field_names():
        mapper = ctx.mappers.mapper(name)
        if getattr(mapper, "type_name", "") == "join":
            return name
    return None


def _shard_ctxs(ctx: SegmentContext):
    """SegmentContexts for EVERY segment of the shard snapshot — join
    queries span segments (parents and children share a shard, not a
    segment). Sibling contexts carry the READER's live masks (the
    point-in-time snapshot), not the segments' current masks, so
    mid-scroll deletes stay invisible exactly as in query_shard. Falls
    back to just this segment without a reader."""
    if ctx.reader is None:
        return [ctx]
    out = []
    for si, (seg, live_host) in enumerate(
            zip(ctx.reader.segments, ctx.reader.live_masks)):
        if seg is ctx.segment:
            out.append(ctx)
            continue
        n_pad = next_pow2(max(seg.n_docs, 1), minimum=BLOCK)
        snap = np.zeros(n_pad, bool)
        snap[: len(live_host)] = live_host
        out.append(SegmentContext(
            seg, ctx.mappers, segment_idx=si,
            doc_count_override=ctx.doc_count_override,
            df_overrides=ctx.df_overrides,
            field_stats_overrides=ctx.field_stats_overrides,
            live_override=jnp.asarray(snap), reader=ctx.reader))
    return out


def _join_cache(ctx: SegmentContext, key: Tuple, build):
    """Shard-level cache for join pre-passes: the wanted-parent/child set
    is identical for every segment of the shard, so compute it once per
    (snapshot, query) instead of O(segments^2) inner executions. Lives on
    the snapshot's first segment, keyed by every segment's uid + live
    count so any refresh/delete invalidates."""
    if ctx.reader is None:
        return build()
    snapshot = tuple((seg.uid, int(np.asarray(m).sum())) for seg, m in
                     zip(ctx.reader.segments, ctx.reader.live_masks))
    return ctx.reader.segments[0].cached_filter(key + (snapshot,), build)


def _relation_mask(seg, join_field: str, relation: str) -> np.ndarray:
    mask = np.zeros(seg.n_docs, bool)
    kf = seg.keywords.get(join_field)
    if kf is not None:
        mask[kf.docs_with_term(relation)] = True
    return mask


def _parent_ids_of(seg, join_field: str, docs: np.ndarray) -> list:
    kf = seg.keywords.get(f"{join_field}#parent")
    out = []
    if kf is None:
        return out
    for d in docs:
        ords = kf.ord_values[kf.ord_offsets[d]: kf.ord_offsets[d + 1]]
        out.extend(kf.term_list[int(o)] for o in ords)
    return out


def _h_has_child(q: dsl.HasChild, ctx: SegmentContext) -> Result:
    """Parents with >= min_children matching children. Children live in
    the same SHARD (routed by parent id) but possibly other segments, so
    the child pass runs over the whole shard snapshot. Matching parents
    score a constant boost (score_mode none — documented divergence from
    the reference's child-score aggregation modes)."""
    join_field = _join_field(ctx)
    if join_field is None:
        return ctx.zeros(), ctx.none_mask()

    def build():
        from collections import Counter
        counts: Counter = Counter()
        for other in _shard_ctxs(ctx):
            seg = other.segment
            child_mask = _relation_mask(seg, join_field, q.child_type)
            if not child_mask.any():
                continue
            _, inner_mask = execute(q.query, other)
            live = np.asarray(other.live)[: seg.n_docs]
            matched = np.asarray(inner_mask)[: seg.n_docs] \
                & child_mask & live
            counts.update(_parent_ids_of(seg, join_field,
                                         np.nonzero(matched)[0]))
        return frozenset(pid for pid, n in counts.items()
                         if n >= q.min_children)

    wanted = _join_cache(
        ctx, ("has_child", q.child_type, q.min_children, repr(q.query)),
        build)
    mask_host = np.zeros(ctx.segment.n_docs, bool)
    for pid in wanted:
        d = ctx.segment.id_to_doc.get(pid)
        if d is not None:
            mask_host[d] = True
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_has_parent(q: dsl.HasParent, ctx: SegmentContext) -> Result:
    """Children whose parent matches the inner query."""
    join_field = _join_field(ctx)
    if join_field is None:
        return ctx.zeros(), ctx.none_mask()

    def build():
        matching: set = set()
        for other in _shard_ctxs(ctx):
            seg = other.segment
            parent_mask = _relation_mask(seg, join_field, q.parent_type)
            if not parent_mask.any():
                continue
            _, inner_mask = execute(q.query, other)
            live = np.asarray(other.live)[: seg.n_docs]
            matched = np.asarray(inner_mask)[: seg.n_docs] \
                & parent_mask & live
            matching.update(seg.ids[d] for d in np.nonzero(matched)[0])
        return frozenset(matching)

    matching_parents = _join_cache(
        ctx, ("has_parent", q.parent_type, repr(q.query)), build)
    seg = ctx.segment

    def project():
        # one CSR pass: docs whose #parent ordinal names a matching parent
        kf = seg.keywords.get(f"{join_field}#parent")
        mask_host = np.zeros(seg.n_docs, bool)
        if kf is not None and matching_parents:
            wanted = np.asarray(
                [tid for term, tid in kf.terms.items()
                 if term in matching_parents], np.int64)
            if len(wanted):
                counts = np.diff(kf.ord_offsets)
                owner = np.repeat(np.arange(len(counts)), counts)
                hit = np.isin(kf.ord_values, wanted)
                mask_host[owner[hit]] = True
        return mask_host

    mask_host = _cached_filter(
        ctx, ("has_parent_proj", join_field,
              tuple(sorted(matching_parents))), project)
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_parent_id(q: dsl.ParentId, ctx: SegmentContext) -> Result:
    join_field = _join_field(ctx)
    if join_field is None:
        return ctx.zeros(), ctx.none_mask()
    seg = ctx.segment
    child_mask = _relation_mask(seg, join_field, q.child_type)
    kf = seg.keywords.get(f"{join_field}#parent")
    mask_host = np.zeros(seg.n_docs, bool)
    if kf is not None:
        mask_host[kf.docs_with_term(q.id)] = True
    mask_host &= child_mask
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_percolate(q: dsl.Percolate, ctx: SegmentContext) -> Result:
    """Reverse search over stored queries (search/percolate.py). Matching
    stored queries score a constant boost (the reference scores with the
    stored query's own score against the document; documented
    divergence)."""
    from elasticsearch_tpu.search.percolate import percolate_segment
    if not q.documents:
        raise QueryParsingError(
            "percolate requires [document] or [documents]")
    mask_host = percolate_segment(ctx, q.field, q.documents)
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_nested(q: dsl.Nested, ctx: SegmentContext) -> Result:
    """Per-object nested matching over _source (search/nested.py).

    The device columns flatten nested arrays — precisely the cross-object
    false match the nested type exists to prevent — so the object-scoped
    constraint runs host-side against the stored sources, like the
    reference's hidden sub-document join (NestedQueryBuilder). Matching
    docs score a constant boost (documented divergence: no per-child BM25)."""
    from elasticsearch_tpu.search.nested import (
        match_object, nested_objects,
    )
    seg = ctx.segment

    def build():
        mask_host = np.zeros(seg.n_docs, bool)
        for d in range(seg.n_docs):
            for obj in nested_objects(seg.sources[d] or {}, q.path):
                if match_object(obj, q.query, q.path):
                    mask_host[d] = True
                    break
        return ctx.to_device_mask(mask_host)

    # the per-object scan is Python-over-_source: cache the mask per
    # (path, query) on the immutable segment so repeated nested queries
    # pay it once (segments never mutate; the LRU-capped filter cache
    # already holds exactly this class of value)
    mask = seg.cached_filter(("nested", q.path, repr(q.query)), build) \
        & ctx.live
    scores = jnp.where(mask, jnp.float32(q.boost), 0.0)
    return scores, mask


_VECTOR_FN = re.compile(
    r"(cosineSimilarity|dotProduct|l2norm)\s*\(\s*params\.(\w+)\s*,\s*'?\"?([\w.]+)'?\"?\s*\)")


def _h_script_score(q: dsl.ScriptScore, ctx: SegmentContext) -> Result:
    """Supports the reference's vector score functions
    (ScoreScriptUtils.java:132,151) plus '+ N' offsets — the dominant
    script_score use in the vector-search benchmark configs."""
    _, base_mask = execute(q.query, ctx)
    m = _VECTOR_FN.search(q.source)
    if not m:
        raise QueryParsingError(
            f"unsupported script_score source [{q.source}]; supported: "
            "cosineSimilarity/dotProduct/l2norm(params.<v>, '<field>') [+ N]")
    fn, param, field_name = m.groups()
    vec = q.params.get(param)
    if vec is None:
        raise QueryParsingError(f"missing script param [{param}]")
    dev = DeviceVectors.for_segment(ctx.segment, field_name)
    if dev is None:
        return ctx.zeros(), ctx.none_mask()
    from elasticsearch_tpu.ops.knn import vector_scores
    qv = jnp.asarray(np.asarray(vec, np.float32))
    if fn == "cosineSimilarity":
        raw = vector_scores(dev.matrix, dev.norms, dev.exists, qv, "cosine")
        raw = raw * 2.0 - 1.0          # undo (1+cos)/2 -> raw cosine
    elif fn == "dotProduct":
        raw = vector_scores(dev.matrix, dev.norms, dev.exists, qv, "dot_product")
        raw = (raw - 0.5) * 2.0        # raw dot
    else:
        raw = vector_scores(dev.matrix, dev.norms, dev.exists, qv, "l2_norm")
        raw = 1.0 / raw - 1.0          # undo 1/(1+d) -> distance
    offset = 0.0
    m_off = re.search(r"\+\s*([\d.]+)\s*$", q.source)
    if m_off:
        offset = float(m_off.group(1))
    scores = (raw + offset) * q.boost
    mask = base_mask & dev.exists & ctx.live
    return jnp.where(mask, scores, 0.0), mask


def _h_function_score(q: dsl.FunctionScore, ctx: SegmentContext) -> Result:
    scores, mask = execute(q.query, ctx)
    fn_vals: List[jnp.ndarray] = []
    for f in q.functions:
        if "weight" in f and len(f) == 1:
            fn_vals.append(jnp.full(ctx.n_docs_pad, float(f["weight"])))
        elif "field_value_factor" in f:
            spec = f["field_value_factor"]
            dv = ctx.segment.doc_values.get(spec["field"])

            def apply_factor(raw):
                v = raw * spec.get("factor", 1.0)
                mod = spec.get("modifier", "none")
                # ES modifiers are base-10 logs (FieldValueFactorFunction.java:
                # LOG=log10(v), LOG1P=log10(v+1), LOG2P=log10(v+2)); the LN
                # family is natural log.
                if mod == "log":
                    v = np.log10(np.maximum(v, 1e-9))
                elif mod == "log1p":
                    v = np.log10(np.maximum(v, 0) + 1)
                elif mod == "log2p":
                    v = np.log10(np.maximum(v, 0) + 2)
                elif mod == "ln":
                    v = np.log(np.maximum(v, 1e-9))
                elif mod == "ln1p":
                    v = np.log1p(np.maximum(v, 0))
                elif mod == "ln2p":
                    v = np.log(np.maximum(v, 0) + 2)
                elif mod == "sqrt":
                    v = np.sqrt(np.maximum(v, 0))
                elif mod == "square":
                    v = v * v
                elif mod == "reciprocal":
                    v = 1.0 / np.maximum(v, 1e-9)
                return v

            # ES applies factor+modifier to `missing` as if read from the doc
            missing_val = float(apply_factor(np.float64(spec.get("missing", 1.0))))
            vals = np.full(ctx.n_docs_pad, missing_val, np.float32)
            if dv is not None:
                v = apply_factor(dv.values.astype(np.float64))
                vals[: len(v)][dv.exists] = v[dv.exists]
            w = float(f.get("weight", 1.0))
            fn_vals.append(jnp.asarray(vals) * w)
        elif "random_score" in f:
            seed = int(f["random_score"].get("seed", 42))
            rng = np.random.default_rng(seed)
            fn_vals.append(jnp.asarray(rng.random(ctx.n_docs_pad, np.float32))
                           * float(f.get("weight", 1.0)))
        else:
            raise QueryParsingError(f"unsupported function_score function {list(f)}")
    if fn_vals:
        stack = jnp.stack(fn_vals)
        if q.score_mode == "multiply":
            fn_total = jnp.prod(stack, axis=0)
        elif q.score_mode == "max":
            fn_total = jnp.max(stack, axis=0)
        elif q.score_mode == "min":
            fn_total = jnp.min(stack, axis=0)
        elif q.score_mode == "avg":
            fn_total = jnp.mean(stack, axis=0)
        else:
            fn_total = jnp.sum(stack, axis=0)
        if q.boost_mode == "multiply":
            scores = scores * fn_total
        elif q.boost_mode == "replace":
            scores = fn_total
        elif q.boost_mode == "sum":
            scores = scores + fn_total
        elif q.boost_mode == "avg":
            scores = (scores + fn_total) / 2.0
        elif q.boost_mode == "max":
            scores = jnp.maximum(scores, fn_total)
        elif q.boost_mode == "min":
            scores = jnp.minimum(scores, fn_total)
    return jnp.where(mask, scores * q.boost, 0.0), mask


def _h_span(q: dsl.SpanQuery, ctx: SegmentContext) -> Result:
    """Position-based span matching (search/spans.py); matched docs score
    a constant boost (documented divergence: the reference scores spans
    with a sloppy-freq similarity)."""
    from elasticsearch_tpu.search.spans import span_field, span_match_mask
    fname = span_field(q)
    pf = ctx.segment.postings.get(fname) if fname else None
    if pf is None:
        return ctx.zeros(), ctx.none_mask()
    mask_host = _cached_filter(
        ctx, ("span", repr(q)), lambda: span_match_mask(
            q, pf, ctx.segment.n_docs))
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_intervals(q: dsl.Intervals, ctx: SegmentContext) -> Result:
    from elasticsearch_tpu.search.spans import intervals_match_mask
    pf = ctx.segment.postings.get(q.field)
    if pf is None:
        return ctx.zeros(), ctx.none_mask()
    analyzer = ctx.search_analyzer(q.field)
    mask_host = _cached_filter(
        ctx, ("intervals", q.field, repr(q.rule)),
        lambda: intervals_match_mask(q, pf, analyzer, ctx.segment.n_docs))
    mask = ctx.to_device_mask(mask_host) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _parsed_string_query(q, ctx: SegmentContext, parse) -> dsl.Query:
    """Parse once per (node, mapper service) and memoize on the node —
    the same QueryString node executes against every segment of the
    shard, and re-parsing per segment is pure waste. Field names inside
    the string surface only after parsing, so aliases resolve on the
    parsed tree; leaves left on the "*" fallback expand to the index's
    searchable fields."""
    from elasticsearch_tpu.search.querystring import expand_star_fields
    cached = getattr(q, "_parsed_cache", None)
    if cached is not None and cached[0] is ctx.mappers:
        return cached[1]
    tree = expand_star_fields(
        resolve_aliases(parse(q), ctx.mappers), ctx.mappers)
    q._parsed_cache = (ctx.mappers, tree)
    return tree


def _h_query_string(q: dsl.QueryString, ctx: SegmentContext) -> Result:
    from elasticsearch_tpu.search.querystring import parse_query_string
    return execute(_parsed_string_query(q, ctx, parse_query_string), ctx)


def _h_simple_query_string(q: dsl.SimpleQueryString,
                           ctx: SegmentContext) -> Result:
    from elasticsearch_tpu.search.querystring import (
        parse_simple_query_string,
    )
    return execute(
        _parsed_string_query(q, ctx, parse_simple_query_string), ctx)


def _implicit_return(src: str) -> str:
    """Expression-style scripts implicitly return their value in filter
    and terms_set contexts. A WORD-boundary check (not substring — a
    field named 'return_count' must not defeat the wrap) on single
    expressions only (wrapping 'a; b' would be invalid syntax)."""
    if ";" not in src and not re.search(r"\breturn\b", src):
        return f"return ({src})"
    return src


def _h_terms_set(q: dsl.TermsSet, ctx: SegmentContext) -> Result:
    """Count matching terms per doc; require >= the per-doc threshold from
    minimum_should_match_field, or from the script evaluated with
    params.num_terms (TermsSetQueryBuilder analog)."""
    seg = ctx.segment

    def build():
        count = np.zeros(seg.n_docs, np.int32)
        for v in q.terms:
            count += _term_mask_host(ctx, q.field, v).astype(np.int32)
        if q.minimum_should_match_field:
            dv = seg.doc_values.get(q.minimum_should_match_field)
            if dv is None:
                return np.zeros(seg.n_docs, bool)
            required = dv.values.astype(np.int64)
            mask = dv.exists & (count >= np.maximum(required, 1)) \
                & (required > 0)
        elif q.minimum_should_match_script is not None:
            from elasticsearch_tpu.script import default_engine
            val = default_engine.execute(
                _implicit_return(q.minimum_should_match_script),
                {"params": {"num_terms": len(q.terms)}})
            required = max(int(val), 1)
            mask = count >= required
        else:
            mask = count >= 1
        return mask

    key = ("terms_set", q.field, tuple(map(str, q.terms)),
           q.minimum_should_match_field, q.minimum_should_match_script)
    mask = ctx.to_device_mask(_cached_filter(ctx, key, build)) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_distance_feature(q: dsl.DistanceFeature, ctx: SegmentContext) -> Result:
    """score = boost * pivot / (pivot + distance(doc, origin)) over a date
    or geo_point field (DistanceFeatureQueryBuilder analog)."""
    seg = ctx.segment
    if q.origin is None or q.pivot is None:
        raise QueryParsingError("distance_feature requires [origin] and [pivot]")
    t = ctx.mappers.field_type(q.field)
    if t == "geo_point":
        pts = _geo_column(ctx, q.field)
        lat = np.radians(pts[:, 0])
        lon = np.radians(pts[:, 1])
        qlat, qlon = np.radians(dsl._parse_geo_point(q.origin))
        a = np.sin((lat - qlat) / 2) ** 2 + \
            np.cos(lat) * np.cos(qlat) * np.sin((lon - qlon) / 2) ** 2
        dist = 2 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0, 1)))
        pivot = dsl.parse_distance_m(q.pivot)
        exists = ~np.isnan(dist)
        dist = np.nan_to_num(dist, nan=np.inf)
    else:
        dv = seg.doc_values.get(q.field)
        if dv is None:
            return ctx.zeros(), ctx.none_mask()
        origin = parse_date_millis(q.origin) if t == "date" \
            else float(q.origin)
        pivot = _parse_time_millis(q.pivot) if t == "date" \
            else float(q.pivot)
        dist = np.abs(dv.values.astype(np.float64) - origin)
        exists = dv.exists
    scores_host = np.zeros(ctx.n_docs_pad, np.float32)
    vals = q.boost * pivot / (pivot + dist)
    scores_host[: seg.n_docs][exists[: seg.n_docs]] = \
        vals[: seg.n_docs][exists[: seg.n_docs]]
    mask = ctx.to_device_mask(exists[: seg.n_docs]) & ctx.live
    return jnp.where(mask, jnp.asarray(scores_host), 0.0), mask


_TIME_UNITS_MS = {"d": 86_400_000.0, "h": 3_600_000.0, "m": 60_000.0,
                  "s": 1000.0, "ms": 1.0, "w": 7 * 86_400_000.0}


def _parse_time_millis(raw: Any) -> float:
    if isinstance(raw, (int, float)):
        return float(raw)
    s = str(raw).strip().lower()
    for suffix in ("ms", "w", "d", "h", "m", "s"):
        if s.endswith(suffix):
            return float(s[: -len(suffix)]) * _TIME_UNITS_MS[suffix]
    return float(s)


def _h_pinned(q: dsl.Pinned, ctx: SegmentContext) -> Result:
    """Pinned ids rank first in list order, above every organic hit
    (x-pack PinnedQueryBuilder: promoted docs get descending constant
    scores above the organic score ceiling)."""
    scores, mask = execute(q.organic, ctx) if q.organic is not None \
        else (ctx.zeros(), ctx.none_mask())
    # cap organic scores below the pinned band; the rank step must exceed
    # the float32 ulp at PIN_BASE (~2.4e31) or ranks collapse together
    PIN_BASE = np.float32(2e38)
    PIN_STEP = np.float32(1e32)
    scores = jnp.minimum(scores, jnp.float32(1e38))
    pin_scores = np.zeros(ctx.n_docs_pad, np.float32)
    pin_mask = np.zeros(ctx.n_docs_pad, bool)
    for rank, doc_id in enumerate(q.ids):
        d = ctx.segment.id_to_doc.get(doc_id)
        if d is not None:
            pin_scores[d] = PIN_BASE - rank * PIN_STEP
            pin_mask[d] = True
    pin_mask_dev = jnp.asarray(pin_mask) & ctx.live
    # boost applies to the organic half only: multiplying the pin band
    # would overflow f32 (2e38 * boost > max) and collapse pin ordering
    scores = jnp.where(pin_mask_dev, jnp.asarray(pin_scores),
                       scores * q.boost)
    return scores, mask | pin_mask_dev


def _h_script_query(q: dsl.ScriptQuery, ctx: SegmentContext) -> Result:
    """Filter-context script per live doc with doc-values access
    (ScriptQueryBuilder analog; scripts run in the sandboxed host
    interpreter, so the mask is cached hard on the segment)."""
    from elasticsearch_tpu.script import default_engine
    seg = ctx.segment

    def build():
        engine = default_engine
        compiled = engine.compile(_implicit_return(q.source))
        mask = np.zeros(seg.n_docs, bool)
        columns = {name: dv for name, dv in seg.doc_values.items()}
        for d in range(seg.n_docs):
            doc = _ScriptDocView(seg, columns, d)
            try:
                mask[d] = bool(compiled.execute(
                    {"doc": doc, "params": dict(q.params)}))
            except Exception:  # noqa: BLE001 — a failing doc just no-matches
                mask[d] = False
        return mask

    key = ("script_query", q.source, repr(sorted(q.params.items())))
    mask = ctx.to_device_mask(_cached_filter(ctx, key, build)) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


class _ScriptDocView:
    """doc['field'].value / doc['field'].values view over segment columns."""

    class _Field:
        __slots__ = ("values",)

        def __init__(self, values):
            self.values = values

        @property
        def value(self):
            return self.values[0] if self.values else None

        @property
        def empty(self):
            return not self.values

        def size(self):
            return len(self.values)

        def __len__(self):
            return len(self.values)

        def __getitem__(self, i):
            return self.values[i]

    def __init__(self, seg, columns, d: int):
        self._seg = seg
        self._columns = columns
        self._d = d

    def __getitem__(self, name: str):
        dv = self._columns.get(name)
        if dv is not None and dv.exists[self._d]:
            # dv.multi holds the FULL value list for multi-valued docs
            # (values[d] is its first entry), matching phase.py/fetch.py
            multi = dv.multi.get(self._d)
            vals = [float(x) for x in multi] if multi is not None \
                else [float(dv.values[self._d])]
            return self._Field(vals)
        kf = self._seg.keywords.get(name)
        if kf is not None:
            ords = kf.ord_values[kf.ord_offsets[self._d]:
                                 kf.ord_offsets[self._d + 1]]
            return self._Field([kf.term_list[int(o)] for o in ords])
        return self._Field([])

    def containsKey(self, name: str) -> bool:  # noqa: N802 — painless API
        return len(self[name].values) > 0


def _h_geo_shape(q: dsl.GeoShape, ctx: SegmentContext) -> Result:
    """Relation test against stored GeoJSON shapes (GeoShapeQueryBuilder
    analog): candidate docs from the columnar centroid-existence check,
    exact relations host-side from _source (search/geoshape.py)."""
    from elasticsearch_tpu.search.geoshape import (
        parse_shape, relation_matches,
    )
    try:
        query_shape = parse_shape(q.shape)
    except Exception as e:  # noqa: BLE001 — malformed query geometry
        raise QueryParsingError(f"failed to parse geo_shape query: {e}")
    seg = ctx.segment

    from elasticsearch_tpu.search.fetch import _field_from_source

    def build():
        # every relation is exists-gated: docs without the field match
        # nothing, including disjoint (the reference's semantics)
        mask = np.zeros(seg.n_docs, bool)
        has = _exists_mask_host(ctx, q.field)
        for d in np.nonzero(has)[0]:
            raw = _field_from_source(seg.sources[d] or {}, q.field)
            if raw is None:
                continue
            try:
                doc_shape = parse_shape(raw)
            except Exception:  # noqa: BLE001 — unparseable stored shape
                continue
            if relation_matches(doc_shape, query_shape, q.relation):
                mask[d] = True
        return ctx.to_device_mask(mask)

    mask = seg.cached_filter(
        ("geo_shape", q.field, repr(q.shape), q.relation), build) \
        & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


def _h_geo_polygon(q: dsl.GeoPolygon, ctx: SegmentContext) -> Result:
    def build():
        pts = _geo_column(ctx, q.field)
        lat, lon = pts[:, 0], pts[:, 1]
        n = len(q.points)
        inside = np.zeros(len(lat), bool)
        # ray casting; NaN rows compare False throughout and stay outside
        j = n - 1
        for i in range(n):
            yi, xi = q.points[i]
            yj, xj = q.points[j]
            cond = ((yi > lat) != (yj > lat)) & \
                (lon < (xj - xi) * (lat - yi) / ((yj - yi) + 1e-12) + xi)
            inside ^= np.where(np.isnan(lat), False, cond)
            j = i
        return ctx.to_device_mask(inside)
    mask = ctx.segment.cached_filter(
        ("geo_polygon", q.field, tuple(q.points)), build) & ctx.live
    return jnp.where(mask, jnp.float32(q.boost), 0.0), mask


_HANDLERS = {
    KnnBound: _h_knn_bound,
    dsl.SpanTerm: _h_span,
    dsl.SpanNear: _h_span,
    dsl.SpanOr: _h_span,
    dsl.SpanNot: _h_span,
    dsl.SpanFirst: _h_span,
    dsl.SpanContaining: _h_span,
    dsl.SpanWithin: _h_span,
    dsl.SpanMulti: _h_span,
    dsl.Intervals: _h_intervals,
    dsl.QueryString: _h_query_string,
    dsl.SimpleQueryString: _h_simple_query_string,
    dsl.TermsSet: _h_terms_set,
    dsl.DistanceFeature: _h_distance_feature,
    dsl.Pinned: _h_pinned,
    dsl.ScriptQuery: _h_script_query,
    dsl.GeoPolygon: _h_geo_polygon,
    dsl.GeoShape: _h_geo_shape,
    dsl.MatchAll: _h_match_all,
    dsl.MatchNone: _h_match_none,
    dsl.Match: _h_match,
    dsl.MultiMatch: _h_multi_match,
    dsl.MatchPhrase: _h_match_phrase,
    dsl.MatchPhrasePrefix: _h_match_phrase_prefix,
    dsl.MoreLikeThis: _h_more_like_this,
    dsl.GeoDistance: _h_geo_distance,
    dsl.GeoBoundingBox: _h_geo_bounding_box,
    dsl.Term: _h_term,
    dsl.Terms: _h_terms,
    dsl.Range: _h_range,
    dsl.Exists: _h_exists,
    dsl.Ids: _h_ids,
    dsl.Prefix: _h_prefix,
    dsl.Wildcard: _h_wildcard,
    dsl.Regexp: _h_regexp,
    dsl.Fuzzy: _h_fuzzy,
    dsl.Bool: _h_bool,
    dsl.ConstantScore: _h_constant_score,
    dsl.DisMax: _h_dis_max,
    dsl.Boosting: _h_boosting,
    dsl.Knn: _h_knn,
    dsl.Nested: _h_nested,
    dsl.HasChild: _h_has_child,
    dsl.HasParent: _h_has_parent,
    dsl.ParentId: _h_parent_id,
    dsl.Percolate: _h_percolate,
    dsl.RankFeature: _h_rank_feature,
    dsl.TextExpansion: _h_text_expansion,
    dsl.ScriptScore: _h_script_score,
    dsl.FunctionScore: _h_function_score,
}
