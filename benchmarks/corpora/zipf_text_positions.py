"""Data kind ``zipf_text_positions``: the ``zipf_text`` corpus with its
positions indexed, queried with the phrase-boost ``bool`` of *Elasticsearch:
The Definitive Guide* ("Proximity for Relevance"): a ``match`` that must
hold ``minimum_should_match`` of the terms and a ``match_phrase`` of the
same text that adds its score where the terms stand together.

The columns ``uterms`` / ``utf`` / ``doc_len`` are ``zipf_text``'s own
generator's (the same seed gives msearch64's documents); ``tokens`` — the
position-indexed term ids, -1 past a document's length — come from the
same draw before it is sorted (:func:`_token_program` repeats that draw;
``benchmarks/tests/test_positions.py`` holds the two to each other).

A query is ``n`` distinct consecutive tokens of a document drawn from the
seed, so every query's phrase occurs at least once; ``n`` follows the
traffic file's shares. Only :func:`install` touches the program (and
:func:`generate` asks it, before any work, whether it can batch phrases
of unequal lengths). The reference (:class:`Reference`) works in float64
on the host from columns regenerated from the seed, and takes nothing
from the program.
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks.corpora import zipf_text as base

K1, B = base.K1, base.B
HEAD, SENTINEL = base.HEAD, base.SENTINEL
term_name = base.term_name
mapping = base.mapping
length_multiset = base.length_multiset
parse_reply = base.parse_reply


# ---------------------------------------------------------------------------
# generation (device)
# ---------------------------------------------------------------------------

def _token_program(rows: int, spec: dict):
    """The jitted draw of one segment's position-indexed tokens → tokens
    (flat, int32, -1 past the length): ``zipf_text._segment_program``'s
    draw of ``lens`` and ``tk`` from the same key, before its sort."""
    import jax
    import jax.numpy as jnp
    vocab, width = int(spec["vocab"]), int(spec["max_len"])
    zc = base.zipf_constants(vocab, float(spec["zipf_s"]))
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
        # one-dimensional, as zipf_text's: no transposition on the host
        return jnp.where(pos < lens[:, None], tk, -1).reshape(-1)

    return jax.jit(gen)


def require_batched_phrases() -> None:
    """A program that plans a ``match_phrase`` of each length as a program
    of its own cannot serve this traffic through the scheduler's batches:
    every round of mixed lengths would fall to the query-by-query path
    and compile inside the window. Say so at once and leave (a parent
    commit from before the padded phrase fails cleanly)."""
    from elasticsearch_tpu.ops import phrase
    if not hasattr(phrase, "exact_phrase_freq"):
        raise SystemExit(
            "this program has no padded exact phrase: phrases of unequal "
            "lengths cannot share a batch, so the phrase-boost traffic "
            "cannot run on it")


def generate(config: dict, seed: int, log=lambda m: None) -> dict:
    """``zipf_text.generate`` plus each segment's ``tokens`` [rows, L]."""
    import time
    import jax
    require_batched_phrases()
    corpus = base.generate(config, seed, log)
    corpus["kind"] = "zipf_text_positions"
    rows = corpus["rows"]
    tok = _token_program(rows, config["corpus"])
    for si, seg in enumerate(corpus["segments"]):
        t0 = time.perf_counter()
        seg["tokens"] = np.asarray(jax.block_until_ready(
            tok(base.seed_key(seed, si)))).reshape(rows, -1)
        log(f"segment {si}: tokens {seg['tokens'].shape} in "
            f"{time.perf_counter() - t0:.2f} s")
    corpus["device_tokens"] = lambda si: tok(base.seed_key(seed, si))
    return corpus


def resident_bytes(config: dict) -> int:
    """Bytes of the columns a batch of the cell's queries reads: the
    BM25 columns and the position matrix (int32, as wide). Shapes only."""
    spec = config["corpus"]
    n = int(spec["segment_rows"]) * int(spec["segments"])
    return base.resident_bytes(config) + n * int(spec["max_len"]) * 4


def stats(corpus: dict) -> dict:
    """What the rooflines count from: documents, postings (a document's
    distinct terms) and positions (its tokens), summed over documents."""
    return {**base.stats(corpus),
            "positions": int(sum(int(seg["doc_len"].sum())
                                 for seg in corpus["segments"]))}


# ---------------------------------------------------------------------------
# into the system under test (the only function that imports the program)
# ---------------------------------------------------------------------------

def install(corpus: dict, node, index: str, log=lambda m: None) -> None:
    import time
    from elasticsearch_tpu.index.segment import Segment
    engine = node.indices_service.indices[index].engine(0)
    names = [term_name(i) for i in range(corpus["vocab"])]
    rows = corpus["rows"]
    for si, seg in enumerate(corpus["segments"]):
        base_id, t0 = si * rows, time.perf_counter()
        engine.install_segment(Segment.from_packed_text(
            0, "body", terms=names, tokens=seg["tokens"],
            uterms=seg["uterms"], utf=seg["utf"], doc_len=seg["doc_len"],
            df=seg["df"], num_docs=rows,
            ids=[str(base_id + i) for i in range(rows)]),
            track_versions=False)
        log(f"install segment {si}: {time.perf_counter() - t0:.2f} s")


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def draw_phrases(corpus: dict, lengths: np.ndarray, rng) -> list:
    """One phrase per entry of ``lengths``: that many consecutive tokens
    of a document drawn from ``rng``, at a start drawn from ``rng``; a
    window that repeats a term (or a document too short) is drawn again.
    → lists of term ids in their order."""
    rows = corpus["rows"]
    segs = corpus["segments"]
    n_docs = rows * len(segs)
    out = []
    for n in lengths:
        n = int(n)
        while True:
            doc = int(rng.integers(n_docs))
            seg = segs[doc // rows]
            length = int(seg["doc_len"][doc % rows])
            if length < n:
                continue
            start = int(rng.integers(length - n + 1))
            window = [int(t) for t in seg["tokens"][doc % rows,
                                                    start:start + n]]
            if len(set(window)) == n:
                out.append(window)
                break
    return out


def query_pool(corpus: dict, params: dict, rng, fixed) -> list:
    """``params["pool"]`` phrases whose lengths follow ``params["terms"]``
    (length → share): the mix (``fixed``) says which entry has which
    length, the same on every seed; the seed (``rng``) draws the
    documents and the windows."""
    lengths = fixed.permutation(length_multiset(params["terms"],
                                                int(params["pool"])))
    return draw_phrases(corpus, lengths, rng)


def request_body(terms: list, size: int, msm: str) -> dict:
    text = " ".join(term_name(t) for t in terms)
    return {"query": {"bool": {
        "must": [{"match": {"body": {"query": text,
                                     "minimum_should_match": msm}}}],
        "should": [{"match_phrase": {"body": text}}]}},
        "size": size}


def request(params: dict, queries: list, index: str) -> dict:
    """One REST request for ``queries`` → path, body, items. ``op`` is
    ``search`` (one query) or ``msearch`` (all of them in one request)."""
    size, msm = int(params["size"]), str(params["minimum_should_match"])
    bodies = [request_body(q, size, msm) for q in queries]
    if params["op"] == "search":
        return {"path": f"/{index}/_search", "items": 1,
                "body": json.dumps(bodies[0])}
    lines = []
    for b in bodies:
        lines += [json.dumps({"index": index}), json.dumps(b)]
    return {"path": "/_msearch", "items": len(bodies),
            "body": "\n".join(lines) + "\n"}


def term_bucket(n: int) -> int:
    """The width the program pads a query's term lists to (2, 4, 8, 12,
    16, ...): its queues and its compiled plans are keyed by it."""
    if n <= 8:
        return 1 << (n - 1).bit_length()
    return -(-n // 4) * 4


def warm_requests(params: dict, pool: list, index: str,
                  max_batch: int) -> list:
    """Requests that reach every program the stream can reach, ONE
    program a request (a request that compiles several outlasts the
    coordinator's stall ceiling on a cold cache). Single searches meet
    in the scheduler's queue of their term bucket and leave as one
    program per bucket and power-of-two batch, up to the connections the
    stream keeps in flight (``params["in_flight"]``) or the scheduler's
    ``max_batch``; an ``_msearch`` of b bodies of one bucket (lengths
    mixed inside it) runs the same program of batch b. Then one plain
    search through the scheduler itself."""
    top = min(int(max_batch), int(params.get("in_flight", max_batch)))
    by_bucket: dict = {}
    for q in pool:
        by_bucket.setdefault(term_bucket(len(q)), []).append(q)
    msearch = {**params, "op": "msearch"}
    out = []
    for _bucket, qs in sorted(by_bucket.items()):
        b = 1
        while b <= top:
            out.append(request(msearch, [qs[j % len(qs)] for j in range(b)],
                               index))
            b *= 2
    return out + [request({**params, "op": "search"}, pool[:1], index)]


# ---------------------------------------------------------------------------
# the plain reference: float64, Lucene's BooleanQuery of the two clauses
# ---------------------------------------------------------------------------

def required_terms(n_terms: int, msm: str) -> int:
    """``minimum_should_match`` as Elasticsearch resolves a percentage of
    ``n_terms`` optional clauses (rounded down), and at least one: a
    should-only BooleanQuery matches nothing without a clause."""
    pct = int(str(msm).rstrip("%"))
    return max(n_terms * pct // 100, 1)


def phrase_counts(tokens, phrase):
    """Occurrences of ``phrase`` (an int32 array of consecutive term ids)
    in each row of ``tokens``, in integers: for every start position at
    which the whole phrase fits, whether each term stands at its
    offset."""
    import jax.numpy as jnp
    n, width = phrase.shape[0], tokens.shape[1]
    at = None
    for k in range(n):
        hit = tokens[:, k:width - n + 1 + k] == phrase[k]
        at = hit if at is None else at & hit
    return at.sum(axis=1, dtype=jnp.int32)


class RefScores(np.ndarray):
    """The reference's score of every document, carrying the phrase's
    frequency in each (``phrase``) and the run's tally of sampled hits
    that hold the phrase (``tally``, shared by every answer of a run)."""
    phrase = None
    tally = None


class Reference(base.Reference):
    """Lucene 5 (Elasticsearch 2.x) over the ``bool`` of two clauses, BM25
    k1 = 1.2, b = 0.75, exact document length, coord 1 (BM25's):

        idf(t)     = ln(1 + (N - df + 0.5) / (df + 0.5))
        w(f, d)    = f · (k1 + 1) / (f + k1·(1 - b + b·|d|/avgdl))
        must(d)    = Σ_t idf(t) · w(tf(t, d), d)     if #{t: tf > 0} ≥ m
        phrase(d)  = (Σ_t idf(t)) · w(pf(d), d)        where pf(d) > 0
        score(d)   = must(d) + phrase(d)               (0: not a hit)

    ``m`` = :func:`required_terms`; ``pf`` = the exact phrase's
    frequency (Lucene's PhraseWeight takes its idf from all its terms).
    Term frequencies (``zipf_text``'s reference) and phrase frequencies
    (:func:`phrase_counts`) are counted from columns regenerated from the
    seed on the device, in integers; every float is float64 on the
    host."""

    def __init__(self, corpus: dict, queries: list, log=lambda m: None,
                 msm: str = "30%"):
        import time
        import jax
        super().__init__(corpus, queries, log)
        self.msm = msm
        t0 = time.perf_counter()
        count = jax.jit(phrase_counts)
        parts: dict = {tuple(q): [] for q in queries}
        for si in range(len(corpus["segments"])):
            tok = corpus["device_tokens"](si).reshape(corpus["rows"], -1)
            for key in parts:
                parts[key].append(np.asarray(count(
                    tok, np.asarray(key, np.int32))).astype(np.uint8))
            del tok
        self.pf = {key: np.concatenate(p) for key, p in parts.items()}
        self.tally = {"answers": 0, "expected": len(queries), "hits": 0}
        log(f"reference: phrase frequencies of {len(parts)} phrases in "
            f"{time.perf_counter() - t0:.2f} s")

    def scores(self, query: list) -> np.ndarray:
        c = self.corpus
        n = c["n_docs"]
        norm = np.concatenate(self.norms)
        must = np.zeros(n, np.float64)
        nmatch = np.zeros(n, np.int32)
        sum_idf = 0.0
        for t in query:
            df = float(c["df"][t])
            idf = np.log1p((n - df + 0.5) / (df + 0.5))
            sum_idf += idf
            tf = np.concatenate([rows[t] for rows in self.tf]).astype(
                np.float64)
            must += idf * tf * (K1 + 1.0) / (tf + norm)
            nmatch += tf > 0
        pf = self.pf[tuple(query)].astype(np.float64)
        phrase = sum_idf * pf * (K1 + 1.0) / (pf + norm)
        hit = nmatch >= required_terms(len(query), self.msm)
        out = np.where(hit, must + phrase, 0.0).view(RefScores)
        out.phrase = self.pf[tuple(query)]
        out.tally = self.tally
        return out


def control_hits(ref: Reference, query: list, k: int):
    """The reference put in the program's place at the nearest precision
    below float32: the same formula over the same exact term and phrase
    frequencies with every float in bfloat16 → (ids, scores, total) as
    the program would answer."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    corpus = ref.corpus
    n = corpus["n_docs"]
    need = required_terms(len(query), ref.msm)
    pf_all = ref.pf[tuple(query)]
    parts, base_row = [], 0
    for seg, rows in zip(corpus["segments"], ref.tf):
        m = len(seg["doc_len"])
        norm = bf(K1) * (bf(1.0 - B) + bf(B) * jnp.asarray(
            seg["doc_len"]).astype(bf) / bf(corpus["avgdl"]))
        s = jnp.zeros(norm.shape, bf)
        nmatch = jnp.zeros(norm.shape, jnp.int32)
        sum_idf = bf(0.0)
        for t in query:
            df = float(corpus["df"][t])
            idf = bf(np.log1p((n - df + 0.5) / (df + 0.5)))
            sum_idf = sum_idf + idf
            tf = jnp.asarray(rows[t]).astype(bf)
            s = s + idf * tf * bf(K1 + 1.0) / (tf + norm)
            nmatch = nmatch + (jnp.asarray(rows[t]) > 0)
        pf = jnp.asarray(pf_all[base_row:base_row + m]).astype(bf)
        s = s + jnp.where(pf > 0, sum_idf * pf * bf(K1 + 1.0) / (pf + norm),
                          bf(0.0))
        s = jnp.where(nmatch >= need, s, bf(0.0))
        parts.append(np.asarray(s.astype(jnp.float32)))
        base_row += m
    full = np.concatenate(parts).astype(np.float64)
    total = int((full > 0).sum())
    kk = min(k, total)
    top = np.argpartition(-full, kk - 1)[:kk] if kk else np.zeros(0, int)
    top = top[np.lexsort((top, -full[top]))]
    return top, full[top], total


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def compare(ref_scores: np.ndarray, params: dict, ids, scores,
            total) -> dict:
    """``zipf_text.compare`` (``score_gap`` relative, ``rank_gap``,
    ``order_wrong``, ``total_wrong``, ``hits_wrong``), and the phrase:
    ``phrase_hits`` — this answer's hits whose document holds the phrase
    (an observation) — and ``phrase_hits_none``, 1 on a run's last answer
    when none of its sampled hits held the phrase: traffic that no longer
    exercises the phrase cannot pass."""
    out = base.compare(ref_scores, params, ids, scores, total)
    phrase, tally = getattr(ref_scores, "phrase", None), \
        getattr(ref_scores, "tally", None)
    if phrase is None or tally is None:
        return out
    ok = (ids >= 0) & (ids < len(phrase))
    out["phrase_hits"] = int((phrase[ids[ok]] > 0).sum())
    tally["answers"] += 1
    tally["hits"] += out["phrase_hits"]
    out["phrase_hits_none"] = int(tally["answers"] >= tally["expected"]
                                  and tally["hits"] == 0)
    return out
