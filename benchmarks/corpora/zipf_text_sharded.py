"""Data kind ``zipf_text_sharded``: the ``zipf_text`` corpus as an index of
several primary shards under Elasticsearch's default ``query_then_fetch``
— every shard scores with ITS OWN df, document count and avgdl, and the
coordinator merges the shards' top-k.

The generator, the query pool, the request builder, the reply parser and
the comparison are ``zipf_text``'s (imported: the same text, the same
queries, the same numbers compared). What differs is what a deployment of
several shards changes:

* :func:`install` puts the segments into the shard engines as contiguous
  id ranges (segments ``[s·p, (s+1)·p)`` of ``p = segments ÷ shards`` into
  shard ``s``): no request of the traffic gets by id, so hashed ``_id``
  routing is not modelled (the configuration's ``assumed``);
* :class:`Reference` is BM25 from the published formula in float64 with
  PER-SHARD statistics: a document's idf and length norm come from the
  shard it lives in, and the expected top-k is the top-k of the union.
  With global statistics (``dfs_query_then_fetch``, which the deployment
  does not use) the order differs — ``benchmarks/tests`` holds a
  hand-worked case;
* :func:`control_hits` is that reference at bfloat16, shard by shard;
* :func:`warm_requests` warms what the window runs: one mixed request.

Only :func:`install` touches the program (and :func:`generate` asks it,
before any work, whether it has the node setting the deployment needs).
"""

from __future__ import annotations

import numpy as np

from benchmarks.corpora import zipf_text as base

K1, B = base.K1, base.B
term_name = base.term_name
mapping = base.mapping
resident_bytes = base.resident_bytes
stats = base.stats
query_pool = base.query_pool
request = base.request
parse_reply = base.parse_reply
compare = base.compare


def require_mesh_setting(config: dict) -> None:
    """A program without the node setting that installs the mesh would
    take ``search.mesh`` for nothing and stack all four shards on one
    chip until it runs out of memory, minutes later: say so at once and
    leave (a parent commit from before the setting fails cleanly)."""
    if "search.mesh" not in config.get("node_settings", {}):
        return
    from elasticsearch_tpu.node import Node
    if not hasattr(Node, "_install_serving_mesh"):
        raise SystemExit(
            "this program has no node setting search.mesh: it cannot "
            "place an index of several shards over several chips, so "
            f"configuration [{config.get('name')}] cannot run on it")


def generate(config: dict, seed: int, log=lambda m: None) -> dict:
    """``zipf_text.generate`` plus each shard's own statistics: ``df``
    [shards, vocab], ``n_docs`` and ``avgdl`` a shard."""
    require_mesh_setting(config)
    spec = config["corpus"]
    n_seg, n_sh = int(spec["segments"]), int(spec["shards"])
    if n_seg % n_sh:
        raise ValueError(f"{n_seg} segments do not divide into {n_sh} "
                         f"shards")
    corpus = base.generate(config, seed, log)
    corpus["kind"] = "zipf_text_sharded"
    corpus["shard_stats"] = shard_stats(
        [seg["df"] for seg in corpus["segments"]],
        [seg["doc_len"] for seg in corpus["segments"]], n_sh)
    return corpus


def shard_stats(seg_df: list, seg_len: list, n_shards: int) -> list:
    """Per shard ``{"df", "n_docs", "avgdl", "segments"}`` from the
    segments' own df and lengths, contiguous runs of segments a shard."""
    per = len(seg_df) // n_shards
    out = []
    for s in range(n_shards):
        mine = list(range(s * per, (s + 1) * per))
        n = sum(len(seg_len[i]) for i in mine)
        tokens = sum(int(np.asarray(seg_len[i], np.int64).sum())
                     for i in mine)
        out.append({"df": sum(np.asarray(seg_df[i], np.int64)
                              for i in mine),
                    "n_docs": n, "avgdl": tokens / n, "segments": mine})
    return out


# ---------------------------------------------------------------------------
# into the system under test (the only function that imports the program)
# ---------------------------------------------------------------------------

def install(corpus: dict, node, index: str, log=lambda m: None) -> None:
    import time
    from elasticsearch_tpu.index.segment import Segment
    svc = node.indices_service.indices[index]
    names = [term_name(i) for i in range(corpus["vocab"])]
    rows = corpus["rows"]
    for sh, st in enumerate(corpus["shard_stats"]):
        engine = svc.engine(sh)
        for local, si in enumerate(st["segments"]):
            seg, base_id, t0 = corpus["segments"][si], si * rows, \
                time.perf_counter()
            engine.install_segment(Segment.from_packed_text(
                local, "body", terms=names, tokens=None,
                uterms=seg["uterms"], utf=seg["utf"],
                doc_len=seg["doc_len"], df=seg["df"], num_docs=rows,
                ids=[str(base_id + i) for i in range(rows)]),
                track_versions=False)
            log(f"install segment {si} into shard {sh}: "
                f"{time.perf_counter() - t0:.2f} s")


def warm_requests(params: dict, pool: list, index: str,
                  max_batch: int) -> list:
    """What the window runs and nothing else: one ``_msearch`` of
    ``items`` queries of mixed lengths is one plane program (every
    ``match`` padded to the request's widest term bucket), so one request
    taken from the pool as the window takes them reaches it."""
    items = int(params.get("items", 1))
    return [request(params, [pool[j % len(pool)] for j in range(items)],
                    index)]


# ---------------------------------------------------------------------------
# the plain reference: BM25, float64, each shard's own statistics
# ---------------------------------------------------------------------------

class Reference(base.Reference):
    """Lucene BM25 (k1 = 1.2, b = 0.75, exact document length) as a shard
    of a ``query_then_fetch`` search computes it — with the statistics of
    the document's OWN shard:

        idf_s(t)  = ln(1 + (N_s - df_s(t) + 0.5) / (df_s(t) + 0.5))
        score(d)  = Σ_t idf_s(t) · tf · (k1 + 1)
                          / (tf + k1·(1 - b + b·|d|/avgdl_s)),   d in s

    Term frequencies are regenerated from the seed as ``zipf_text``'s
    reference does; every float is float64 on the host. It takes nothing
    from the program."""

    def __init__(self, corpus: dict, queries: list, log=lambda m: None):
        super().__init__(corpus, queries, log)
        self.seg_shard = {}
        for sh, st in enumerate(corpus["shard_stats"]):
            for si in st["segments"]:
                self.seg_shard[si] = st
        self.norms = [K1 * (1.0 - B + B * seg["doc_len"].astype(np.float64)
                            / self.seg_shard[si]["avgdl"])
                      for si, seg in enumerate(corpus["segments"])]

    def scores(self, query: list) -> np.ndarray:
        """→ float64 score of every document, by global id (0 = no query
        term)."""
        parts = []
        for si, (norm, rows) in enumerate(zip(self.norms, self.tf)):
            st = self.seg_shard[si]
            s = np.zeros(len(norm), np.float64)
            for t in query:
                df = float(st["df"][t])
                if df <= 0:
                    continue               # the shard holds no such term
                idf = np.log1p((st["n_docs"] - df + 0.5) / (df + 0.5))
                tf = rows[t].astype(np.float64)
                part = tf + norm          # tf·(k1+1)/(tf+norm), in place
                np.divide(tf, part, out=part)
                part *= idf * (K1 + 1.0)
                s += part
            parts.append(s)
        return np.concatenate(parts)


def control_hits(ref: Reference, query: list, k: int):
    """The reference in the program's place at the nearest precision
    below float32: the same formula over the same exact term frequencies
    and the same per-shard statistics with every float in bfloat16 →
    (ids, scores, total) as the program would answer."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    corpus = ref.corpus
    parts = []
    for si, (seg, rows) in enumerate(zip(corpus["segments"], ref.tf)):
        st = ref.seg_shard[si]
        norm = bf(K1) * (bf(1.0 - B) + bf(B) * jnp.asarray(
            seg["doc_len"]).astype(bf) / bf(st["avgdl"]))
        s = jnp.zeros(norm.shape, bf)
        for t in query:
            df = float(st["df"][t])
            if df <= 0:
                continue
            idf = bf(np.log1p((st["n_docs"] - df + 0.5) / (df + 0.5)))
            tf = jnp.asarray(rows[t]).astype(bf)
            s = s + idf * tf * bf(K1 + 1.0) / (tf + norm)
        parts.append(np.asarray(s.astype(jnp.float32)))
    full = np.concatenate(parts).astype(np.float64)
    total = int((full > 0).sum())
    kk = min(k, total)
    top = np.argpartition(-full, kk - 1)[:kk] if kk else np.zeros(0, int)
    top = top[np.lexsort((top, -full[top]))]
    return top, full[top], total
