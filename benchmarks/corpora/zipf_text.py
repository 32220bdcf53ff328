"""Data kind ``zipf_text``: an MS-MARCO-shaped text corpus made on the
device from ``--seed``, the queries drawn from it, and its plain BM25
reference and lower-precision control.

The statistics are those of ``bench.make_corpus(realistic=True)`` (copied
arithmetic, nothing imported): bounded Zipf over the vocabulary, log-normal
document lengths clipped to ``[min_len, max_len]``. The layout is the
engine's forward one: per document the unique terms (``uterms``, -1 = empty
slot) and their counts (``utf``), on an axis as wide as the longest document
may be, so it never truncates and has the same width on every seed.

Only :func:`install` touches the program. The reference (:class:`Reference`)
and the control take the columns this file generated and nothing the
program made.
"""

from __future__ import annotations

import json

import numpy as np

HEAD = 32            # ranks drawn from the exact head of the Zipf CDF
SENTINEL = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# generation (device)
# ---------------------------------------------------------------------------

def zipf_constants(vocab: int, s: float) -> dict:
    """Exact head CDF (ranks 1..HEAD) and the analytic tail's constants of
    P(rank) ∝ rank^-s over ranks [1, vocab)."""
    w = np.arange(1, vocab, dtype=np.float64) ** -s
    total = w.sum()
    head_cdf = np.cumsum(w[:HEAD]) / total
    lo, hi = HEAD + 0.5, vocab - 0.5
    return {"head_cdf": head_cdf.astype(np.float32),
            "head_mass": float(head_cdf[-1]),
            "a": lo ** (1.0 - s), "b": hi ** (1.0 - s),
            "inv": 1.0 / (1.0 - s), "top_share": float(w[0] / total)}


def seed_key(seed: int, stream: int):
    """A threefry key from a seed of up to 64 bits and a stream number."""
    import jax
    import jax.numpy as jnp
    data = jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     dtype=jnp.uint32)
    return jax.random.fold_in(
        jax.random.wrap_key_data(data, impl="threefry2x32"), stream)


def _segment_program(rows: int, spec: dict):
    """The jitted generator of one segment → (uterms, utf, doc_len)."""
    import jax
    import jax.numpy as jnp
    vocab, width = int(spec["vocab"]), int(spec["max_len"])
    zc = zipf_constants(vocab, float(spec["zipf_s"]))
    head_cdf = jnp.asarray(zc["head_cdf"])
    mu, sigma = float(np.log(spec["len_median"])), float(spec["len_sigma"])

    def gen(key):
        k_len, k_tok = jax.random.split(key)
        lens = jnp.clip(jnp.exp(mu + sigma * jax.random.normal(
            k_len, (rows,))), spec["min_len"], width).astype(jnp.int32)
        u = jax.random.uniform(k_tok, (rows, width))
        head = (u[:, :, None] >= head_cdf[None, None, :]).sum(
            axis=-1, dtype=jnp.int32) + 1
        v = (u - zc["head_mass"]) / (1.0 - zc["head_mass"])
        tail = jnp.power(zc["a"] + v * (zc["b"] - zc["a"]), zc["inv"])
        tail = jnp.clip(jnp.round(tail), HEAD + 1, vocab - 1).astype(
            jnp.int32)
        tk = jnp.where(u < zc["head_mass"], head, tail)
        pos = jnp.arange(width, dtype=jnp.int32)[None, :]
        tk = jnp.where(pos < lens[:, None], tk, SENTINEL)
        st = jnp.sort(tk, axis=1)
        real = st != SENTINEL
        first = jnp.concatenate(
            [jnp.ones((rows, 1), bool), st[:, 1:] != st[:, :-1]], axis=1)
        # a run ends where the next run (or the padding) starts
        starts = jnp.where(first, pos, width)
        nxt = jax.lax.cummin(starts, axis=1, reverse=True)
        nxt = jnp.concatenate(
            [nxt[:, 1:], jnp.full((rows, 1), width, jnp.int32)], axis=1)
        first = first & real
        uterms = jnp.where(first, st, -1)
        utf = jnp.where(first, nxt - pos, 0).astype(jnp.float32)
        return uterms, utf, lens

    def flat(key):
        # one-dimensional results: the device keeps a [rows, width] array
        # column-major, and its copy to the host would then need a
        # transposition there (4.4 s a segment, my chip run, PR 25)
        uterms, utf, lens = gen(key)
        return uterms.reshape(-1), utf.reshape(-1), lens

    return jax.jit(flat)


def generate(config: dict, seed: int, log=lambda m: None) -> dict:
    """The corpus of ``config["corpus"]`` from ``seed`` → columns on the
    host, one entry per segment, plus the collection statistics."""
    import time
    import jax
    spec = config["corpus"]
    rows, n_seg = int(spec["segment_rows"]), int(spec["segments"])
    vocab = int(spec["vocab"])
    gen = _segment_program(rows, spec)
    segs, df = [], np.zeros(vocab, np.int64)
    total_tokens = 0
    for si in range(n_seg):
        t0 = time.perf_counter()
        ut, tf, ln = jax.block_until_ready(gen(seed_key(seed, si)))
        t1 = time.perf_counter()
        ut = np.asarray(ut).reshape(rows, -1)
        tf, ln = np.asarray(tf).reshape(rows, -1), np.asarray(ln)
        t2 = time.perf_counter()
        seg_df = np.bincount(ut[ut >= 0], minlength=vocab)
        df += seg_df
        total_tokens += int(ln.sum())
        segs.append({"uterms": ut, "utf": tf, "doc_len": ln, "df": seg_df})
        log(f"segment {si}: {rows} rows, width {ut.shape[1]}: device "
            f"{t1 - t0:.2f} s, to host {t2 - t1:.2f} s, df "
            f"{time.perf_counter() - t2:.2f} s")
    n_docs = rows * n_seg
    return {"kind": "zipf_text", "segments": segs, "df": df,
            "n_docs": n_docs, "avgdl": total_tokens / n_docs,
            "vocab": vocab, "rows": rows, "width": int(spec["max_len"]),
            # the same columns again, on the device, for the reference
            "device_columns": lambda si: gen(seed_key(seed, si))}


def term_name(tid: int) -> str:
    return f"t{tid:06d}"


def resident_bytes(config: dict) -> int:
    """Bytes of the columns a BM25 batch has to read once: uterms (int32),
    utf (float32) and doc_len (int32) of every segment. Shapes only."""
    spec = config["corpus"]
    n = int(spec["segment_rows"]) * int(spec["segments"])
    return n * int(spec["max_len"]) * 8 + n * 4


# ---------------------------------------------------------------------------
# into the system under test (the only function that imports the program)
# ---------------------------------------------------------------------------

def mapping(config: dict) -> dict:
    return config["index"]


def install(corpus: dict, node, index: str, log=lambda m: None) -> None:
    import time
    from elasticsearch_tpu.index.segment import Segment
    engine = node.indices_service.indices[index].engine(0)
    names = [term_name(i) for i in range(corpus["vocab"])]
    rows = corpus["rows"]
    for si, seg in enumerate(corpus["segments"]):
        base, t0 = si * rows, time.perf_counter()
        engine.install_segment(Segment.from_packed_text(
            0, "body", terms=names, tokens=None, uterms=seg["uterms"],
            utf=seg["utf"], doc_len=seg["doc_len"], df=seg["df"],
            num_docs=rows, ids=[str(base + i) for i in range(rows)]),
            track_versions=False)
        log(f"install segment {si}: {time.perf_counter() - t0:.2f} s")


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def length_multiset(weights: dict, n: int) -> np.ndarray:
    """``n`` query lengths in the proportions of ``weights`` — the same
    multiset on every seed (the seed only orders it)."""
    lens = sorted(int(k) for k in weights)
    w = np.array([float(weights[str(k)]) for k in lens])
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[np.argmax(w)] += n - counts.sum()
    return np.repeat(lens, counts)


def draw_queries(corpus: dict, lengths: np.ndarray, rng) -> list:
    """One query of distinct df-weighted terms per entry of ``lengths``."""
    df = corpus["df"]
    present = np.flatnonzero(df > 0)
    cdf = np.cumsum(df[present].astype(np.float64))
    cdf /= cdf[-1]
    lmax = int(lengths.max())
    draws = present[np.searchsorted(
        cdf, rng.random((len(lengths), 3 * lmax + 8)))]
    out = []
    for row, n in zip(draws, lengths):
        seen: list = []
        for t in row:
            if t not in seen:
                seen.append(int(t))
                if len(seen) == n:
                    break
        if len(seen) < n:                # vanishingly rare: fill by rank
            seen += [int(t) for t in present if t not in seen][:n - len(seen)]
        out.append(seen)
    return out


def request_body(terms: list, size: int) -> dict:
    return {"query": {"match": {"body": " ".join(
        term_name(t) for t in terms)}}, "size": size}


def query_pool(corpus: dict, params: dict, rng, fixed) -> list:
    """The pool of distinct queries a stream asks from: ``params["pool"]``
    queries whose lengths follow ``params["terms"]`` (length → share). The
    mix (``fixed``) says which entry has which length, the same on every
    seed; the seed (``rng``) draws the terms."""
    lengths = fixed.permutation(length_multiset(params["terms"],
                                                int(params["pool"])))
    return draw_queries(corpus, lengths, rng)


def request(params: dict, queries: list, index: str) -> dict:
    """One REST request for ``queries`` → path, body, items. ``op`` is
    ``search`` (one query) or ``msearch`` (all of them in one request)."""
    size = int(params["size"])
    bodies = [request_body(q, size) for q in queries]
    if params["op"] == "search":
        return {"path": f"/{index}/_search", "items": 1,
                "body": json.dumps(bodies[0])}
    lines = []
    for b in bodies:
        lines += [json.dumps({"index": index}), json.dumps(b)]
    return {"path": "/_msearch", "items": len(bodies),
            "body": "\n".join(lines) + "\n"}


def warm_requests(params: dict, pool: list, index: str,
                  max_batch: int) -> list:
    """Requests that reach every compiled program a stream of ``params``
    over ``pool`` can reach, each reaching ONE program it may have to
    compile (a request that compiles several outlasts the coordinator's
    35 s stall ceiling on a cold cache and is answered with errors). The
    engine compiles one program per exact number of query terms and
    power-of-two batch. An ``_msearch`` whose queries share a length runs
    as one batch of its size; one of mixed lengths shares no plan and is
    served query by query, each as a batch of one. Single searches meet
    in the scheduler: every power of two up to its ``max_batch``, and the
    query-by-query path a batch of mixed lengths declines to."""
    by_len: dict = {}
    for q in pool:
        by_len.setdefault(len(q), []).append(q)
    msearch = {**params, "op": "msearch"}
    if params["op"] == "msearch" and len(by_len) == 1:
        return [request(msearch, [pool[j % len(pool)] for j in range(
            int(params["items"]))], index)]
    top = 1 if params["op"] == "msearch" else max_batch
    out = []
    for _ln, qs in sorted(by_len.items()):
        b = 1
        while b <= top:
            out.append(request(msearch, [qs[j % len(qs)]
                                         for j in range(b)], index))
            b *= 2
    if params["op"] == "msearch":
        return out
    mixed = [qs[0] for _ln, qs in sorted(by_len.items())][:2]
    return out + [request(msearch, mixed, index),
                  request(params, pool[:1], index)]


def stats(corpus: dict) -> dict:
    """What the rooflines count from: documents, and postings (a
    document's distinct terms, summed over the documents)."""
    return {"docs": int(corpus["n_docs"]),
            "postings": int(corpus["df"].sum())}


# ---------------------------------------------------------------------------
# the plain reference: BM25 from the published formula, float64
# ---------------------------------------------------------------------------

K1, B = 1.2, 0.75


class Reference:
    """Lucene BM25 (k1 = 1.2, b = 0.75, exact document length):

        idf(t)   = ln(1 + (N - df + 0.5) / (df + 0.5))
        score(d) = Σ_t idf(t) · tf · (k1 + 1) / (tf + k1·(1 - b + b·|d|/avgdl))

    Term frequencies are read from the generated columns segment by
    segment with plain ``jax.numpy`` (integers, exact); every float is
    float64 on the host."""

    def __init__(self, corpus: dict, queries: list, log=lambda m: None):
        import time
        self.corpus = corpus
        self.terms = sorted({t for q in queries for t in q})
        self.norms = [K1 * (1.0 - B + B * seg["doc_len"].astype(np.float64)
                            / corpus["avgdl"]) for seg in corpus["segments"]]
        t0 = time.perf_counter()
        self.tf = self._term_frequencies()
        log(f"reference: term frequencies of {len(self.terms)} terms in "
            f"{time.perf_counter() - t0:.2f} s")

    def _term_frequencies(self) -> list:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def tf_rows(uterms, utf, terms):
            def one(t):
                return jnp.sum(jnp.where(uterms == t, utf, 0.0),
                               axis=1).astype(jnp.uint8)
            return jax.lax.map(one, terms)

        out = []
        block = 64
        for si in range(len(self.corpus["segments"])):
            # made again from the seed on the device: the same program
            # gives the same columns, and nothing is uploaded
            ut, tf, _ = self.corpus["device_columns"](si)
            ut = ut.reshape(self.corpus["rows"], -1)
            tf = tf.reshape(self.corpus["rows"], -1)
            rows = {}
            for lo in range(0, len(self.terms), block):
                chunk = self.terms[lo:lo + block]
                padded = chunk + [chunk[-1]] * (block - len(chunk))
                got = np.asarray(tf_rows(ut, tf, jnp.asarray(
                    padded, jnp.int32)))
                for i, t in enumerate(chunk):
                    rows[t] = got[i]
            out.append(rows)
            del ut, tf
        return out

    def scores(self, query: list) -> np.ndarray:
        """→ float64 score of every document (0 = no query term)."""
        c = self.corpus
        n = c["n_docs"]
        parts = []
        for norm, rows in zip(self.norms, self.tf):
            s = np.zeros(len(norm), np.float64)
            for t in query:
                df = float(c["df"][t])
                idf = np.log1p((n - df + 0.5) / (df + 0.5))
                tf = rows[t].astype(np.float64)
                part = tf + norm          # tf·(k1+1)/(tf+norm), in place
                np.divide(tf, part, out=part)
                part *= idf * (K1 + 1.0)
                s += part
            parts.append(s)
        return np.concatenate(parts)


def control_hits(ref: Reference, query: list, k: int):
    """The reference put in the program's place at the nearest precision
    below float32: the same formula over the same exact term frequencies
    with every float in bfloat16 → (ids, scores, total) as the program
    would answer."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    corpus = ref.corpus
    n = corpus["n_docs"]
    parts = []
    for seg, rows in zip(corpus["segments"], ref.tf):
        norm = bf(K1) * (bf(1.0 - B) + bf(B) * jnp.asarray(
            seg["doc_len"]).astype(bf) / bf(corpus["avgdl"]))
        s = jnp.zeros(norm.shape, bf)
        for t in query:
            df = float(corpus["df"][t])
            idf = bf(np.log1p((n - df + 0.5) / (df + 0.5)))
            tf = jnp.asarray(rows[t]).astype(bf)
            s = s + idf * tf * bf(K1 + 1.0) / (tf + norm)
        parts.append(np.asarray(s.astype(jnp.float32)))
    full = np.concatenate(parts).astype(np.float64)
    total = int((full > 0).sum())
    kk = min(k, total)
    top = np.argpartition(-full, kk - 1)[:kk] if kk else np.zeros(0, int)
    top = top[np.lexsort((top, -full[top]))]
    return top, full[top], total


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def parse_reply(reply: dict):
    hits = reply["hits"]["hits"]
    return (np.array([int(h["_id"]) for h in hits], np.int64),
            np.array([h["_score"] for h in hits], np.float64),
            int(reply["hits"]["total"]))


def compare(ref_scores: np.ndarray, params: dict, ids, scores,
            total) -> dict:
    """One answer to a request of ``params`` against the reference's
    scores of every document → the numbers compared (see PERF.md, "How
    correct is decided")."""
    k = int(params["size"])
    n_match = int((ref_scores > 0).sum())
    want = min(k, n_match)
    out = {"score_gap": 0.0, "rank_gap": 0.0,
           "total_wrong": int(total != n_match),
           "hits_wrong": int(len(ids) != want or len(set(ids.tolist()))
                             != len(ids)),
           "order_wrong": 0, "ties_not_by_id": 0}
    if len(ids) == 0 or want == 0:
        return out
    if ids.min() < 0 or ids.max() >= len(ref_scores):
        out["hits_wrong"] = 1
        return out
    # the order, held to the served scores themselves: best first. Among
    # hits of one score Elasticsearch promises its users no order; Lucene
    # (and this program's ops/topk.py) put the lower document id first,
    # so that is counted too, as an observation without a limit
    out["order_wrong"] = int((scores[:-1] < scores[1:]).sum())
    out["ties_not_by_id"] = int(((scores[:-1] == scores[1:])
                                 & (ids[:-1] > ids[1:])).sum())
    mine = ref_scores[ids]
    out["score_gap"] = float(np.max(
        np.abs(scores - mine) / np.maximum(mine, 1e-9)))
    kth = float(np.partition(ref_scores, -want)[-want])
    out["rank_gap"] = float(max(0.0, kth - float(mine.min())) / kth)
    return out
