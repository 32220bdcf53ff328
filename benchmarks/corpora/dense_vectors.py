"""Data kind ``dense_vectors``: a clustered corpus of float32 unit vectors
made on the device from ``--seed``, queries that are noisy copies of
corpus vectors, and the plain cosine reference with its bfloat16 control.

The corpus is a mixture: ``centres`` Gaussian directions on the unit
sphere, each vector one centre plus isotropic Gaussian noise of squared
length ``t`` (drawn per vector from ``[noise_lo, noise_hi]``),
re-normalized in float32. Two vectors of one cluster then meet at a cosine
near ``1 / sqrt((1 + t1)(1 + t2))``: 0.5 to 0.9 for t in [0.1, 1.0], as
sentence embeddings of one topic do, and a query's ten best are its source
and the source's cluster mates, a few 1e-4 to 1e-3 apart — far enough for
float32, close enough that a bfloat16 product moves their scores by 1e-4
and more. (Uniform vectors on the sphere leave every top-k a coin toss
among 3 M scores of 0.18 ± 0.01.)

Only :func:`install` touches the program. The reference
(:class:`Reference`) and the control regenerate the vectors from the seed,
block by block, and take nothing the program made or was given.
"""

from __future__ import annotations

import io
import json

import numpy as np

BLOCK_ROWS = 1 << 17        # rows one generator call makes (403 MB at 768-d)
REF_ROWS = 1 << 15          # rows the reference turns into float64 at once
DECIMALS = 6                # of a query component in a request body


# ---------------------------------------------------------------------------
# generation (device)
# ---------------------------------------------------------------------------

def seed_key(seed: int, stream: int):
    """A threefry key from a seed of up to 64 bits and a stream number."""
    import jax
    import jax.numpy as jnp
    data = jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     dtype=jnp.uint32)
    return jax.random.fold_in(
        jax.random.wrap_key_data(data, impl="threefry2x32"), stream)


def _programs(spec: dict, block: int):
    """→ (centres(key) → [C, D], block(key, centres) → flat [block · D])."""
    import jax
    import jax.numpy as jnp
    dims, n_c = int(spec["dims"]), int(spec["centres"])
    lo, hi = float(spec["noise_lo"]), float(spec["noise_hi"])

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))

    def centres(key):
        return unit(jax.random.normal(key, (n_c, dims), jnp.float32))

    def one_block(key, cen):
        k_a, k_t, k_n = jax.random.split(key, 3)
        assign = jax.random.randint(k_a, (block,), 0, n_c)
        t = jax.random.uniform(k_t, (block,), jnp.float32, lo, hi)
        noise = jax.random.normal(k_n, (block, dims), jnp.float32) \
            * jnp.sqrt(t / dims)[:, None]
        # flat: a [rows, dims] result may leave the device in a tiled
        # layout that the host would have to transpose (zipf_text.py)
        return unit(cen[assign] + noise).reshape(-1)

    return jax.jit(centres), jax.jit(one_block)


def generate(config: dict, seed: int, log=lambda m: None) -> dict:
    """The corpus of ``config["corpus"]`` from ``seed`` → one float32
    ``[rows, dims]`` array a segment on the host (the ONE host copy: the
    program is handed these arrays and must not copy them again)."""
    import time
    import jax
    spec = config["corpus"]
    rows, n_seg = int(spec["segment_rows"]), int(spec["segments"])
    dims = int(spec["dims"])
    block = min(BLOCK_ROWS, rows)
    if rows % block:
        raise ValueError("segment_rows must be a multiple of the block")
    make_centres, make_block = _programs(spec, block)
    cen = make_centres(seed_key(seed, 1 << 20))

    def device_block(si: int, bi: int):
        """Rows [bi·block, (bi+1)·block) of segment ``si``, again, on the
        device: the same program on the same key gives the same bits."""
        return make_block(seed_key(seed, si * (rows // block) + bi),
                          cen).reshape(block, dims)

    segs = []
    for si in range(n_seg):
        t0 = time.perf_counter()
        vecs = np.empty((rows, dims), np.float32)
        for bi in range(rows // block):
            vecs[bi * block:(bi + 1) * block] = np.asarray(
                jax.block_until_ready(device_block(si, bi)))
        segs.append({"vecs": vecs})
        log(f"segment {si}: {rows} rows x {dims}: "
            f"{time.perf_counter() - t0:.2f} s")
    probe = segs[0]["vecs"][:4096].astype(np.float64)
    log("unit length of the generated rows, worst | |v|^2 - 1 | of 4096: "
        f"{np.abs((probe * probe).sum(axis=1) - 1.0).max():.3e}")
    return {"kind": "dense_vectors", "segments": segs,
            "n_docs": rows * n_seg, "rows": rows, "dims": dims,
            "block": block, "field": spec["field"],
            "query_noise": float(spec["query_noise"]),
            "device_block": device_block}


def stats(corpus: dict) -> dict:
    """What the rooflines count from."""
    return {"docs": int(corpus["n_docs"]), "dims": int(corpus["dims"])}


# ---------------------------------------------------------------------------
# into the system under test (the only function that imports the program)
# ---------------------------------------------------------------------------

def mapping(config: dict) -> dict:
    return config["index"]


def install(corpus: dict, node, index: str, log=lambda m: None) -> None:
    import time
    from elasticsearch_tpu.index.segment import Segment
    engine = node.indices_service.indices[index].engine(0)
    rows = corpus["rows"]
    exists = np.ones(rows, bool)
    for si, seg in enumerate(corpus["segments"]):
        base, t0 = si * rows, time.perf_counter()
        engine.install_segment(Segment.from_packed_vectors(
            0, corpus["field"], seg["vecs"], exists.copy(), rows,
            ids=[str(base + i) for i in range(rows)]),
            track_versions=False)
        log(f"install segment {si}: {time.perf_counter() - t0:.2f} s")


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def query_pool(corpus: dict, params: dict, rng, fixed) -> list:
    """``params["pool"]`` queries, each a noisy copy of a corpus vector
    drawn from the seed: ``source + n``, n isotropic Gaussian of squared
    length ``query_noise`` (the source is then met at a cosine near
    ``1 / sqrt(1 + query_noise)``). A query IS the decimal text of its
    components as the request body carries them (``DECIMALS`` places):
    the program and the reference read the same numbers. → list of
    ``{"id", "source", "text"}``."""
    n, dims, rows = int(params["pool"]), corpus["dims"], corpus["rows"]
    src = rng.integers(0, corpus["n_docs"], size=n)
    base = np.stack([corpus["segments"][j // rows]["vecs"][j % rows]
                     for j in src]).astype(np.float64)
    q = base + rng.standard_normal((n, dims)) * np.sqrt(
        corpus["query_noise"] / dims)
    buf = io.StringIO()
    np.savetxt(buf, q, fmt=f"%.{DECIMALS}f", delimiter=",")
    return [{"id": i, "source": int(j), "text": line}
            for i, (j, line) in enumerate(zip(src,
                                              buf.getvalue().splitlines()))]


def query_vector(query: dict) -> np.ndarray:
    """The numbers of a query as the request body states them, float64."""
    return np.array(query["text"].split(","), np.float64)


def request_body(params: dict, query: dict, field: str) -> str:
    return ('{"knn":{"field":"%s","query_vector":[%s],"k":%d,'
            '"num_candidates":%d},"size":%d}' % (
                field, query["text"], int(params["k"]),
                int(params["num_candidates"]), int(params["size"])))


def request(params: dict, queries: list, index: str) -> dict:
    """One REST request for ``queries`` → path, body, items. ``op`` is
    ``search`` (one query) or ``msearch`` (all of them in one request)."""
    bodies = [request_body(params, q, params["field"]) for q in queries]
    if params["op"] == "search":
        return {"path": f"/{index}/_search", "items": 1, "body": bodies[0]}
    head = json.dumps({"index": index})
    return {"path": "/_msearch", "items": len(bodies),
            "body": "".join(f"{head}\n{b}\n" for b in bodies)}


def warm_requests(params: dict, pool: list, index: str,
                  max_batch: int) -> list:
    """Requests that reach every compiled program the stream can reach,
    ONE program a request (a request that compiles several outlasts the
    coordinator's stall ceiling on a cold cache). Single searches meet in
    the scheduler's ``knn`` queue and leave as one program per
    power-of-two batch, up to the connections the stream keeps in flight
    (``params["in_flight"]``) or the scheduler's ``max_batch``; an
    ``_msearch`` of b knn bodies runs the same program of batch b. Then
    one plain search through the scheduler itself."""
    top = min(int(max_batch), int(params.get("in_flight", max_batch)))
    msearch = {**params, "op": "msearch"}
    out, b = [], 1
    while b <= top:
        out.append(request(msearch, [pool[j % len(pool)]
                                     for j in range(b)], index))
        b *= 2
    return out + [request({**params, "op": "search"}, pool[:1], index)]


# ---------------------------------------------------------------------------
# the plain reference: cosine from its definition, float64
# ---------------------------------------------------------------------------

class Reference:
    """``cos(q, v) = q·v / (|q| |v|)`` with the query's stated decimals
    and the generator's float32 vectors both taken to float64 first:
    every product, sum, norm and quotient is float64 on the host. The
    vectors are made again from the seed on the device (bits, exact) and
    brought over a block at a time."""

    def __init__(self, corpus: dict, queries: list, log=lambda m: None):
        import time
        t0 = time.perf_counter()
        self.corpus = corpus
        self.slot = {q["id"]: i for i, q in enumerate(queries)}
        qm = np.stack([query_vector(q) for q in queries])
        self.qn = qm / np.linalg.norm(qm, axis=1, keepdims=True)
        self.full = np.empty((len(queries), corpus["n_docs"]), np.float64)
        rows, block = corpus["rows"], corpus["block"]
        for si in range(len(corpus["segments"])):
            for bi in range(rows // block):
                got = np.asarray(corpus["device_block"](si, bi))
                for lo in range(0, block, REF_ROWS):
                    v = got[lo:lo + REF_ROWS].astype(np.float64)
                    norm = np.sqrt(np.einsum("ij,ij->i", v, v))
                    at = si * rows + bi * block + lo
                    self.full[:, at:at + len(v)] = (self.qn @ v.T) / norm
        first = sum(int(np.argmax(self.full[i])) == q["source"]
                    for i, q in enumerate(queries))
        log(f"reference: {len(queries)} queries x {corpus['n_docs']} "
            f"vectors in float64 in {time.perf_counter() - t0:.2f} s; "
            f"the source document is the best hit of {first}")

    def scores(self, query: dict) -> np.ndarray:
        """→ float64 cosine of every document."""
        return self.full[self.slot[query["id"]]]


def control_hits(ref: Reference, query: dict, k: int):
    """The reference put in the program's place at the nearest precision
    below the configuration's: the generator's float32 unit vectors and
    the float32 unit query both ROUNDED TO bfloat16 before the product
    (one MXU pass, float32 accumulation) — what XLA's default precision
    makes of a float32 matmul on a TPU → (ids, scores, total) as the
    program would answer. The first call scores all of the reference's
    queries in one walk over the regenerated blocks."""
    import jax
    import jax.numpy as jnp
    corpus = ref.corpus
    if not hasattr(ref, "one_pass"):
        qn = jnp.asarray(ref.qn, jnp.float32).astype(jnp.bfloat16)

        @jax.jit
        def one_pass(v):
            return jnp.dot(qn, v.astype(jnp.bfloat16).T,
                           preferred_element_type=jnp.float32)

        rows, block = corpus["rows"], corpus["block"]
        ref.one_pass = np.concatenate(
            [np.asarray(one_pass(corpus["device_block"](si, bi)))
             for si in range(len(corpus["segments"]))
             for bi in range(rows // block)], axis=1)
    full = ref.one_pass[ref.slot[query["id"]]].astype(np.float64)
    kk = min(k, len(full))
    top = np.argpartition(-full, kk - 1)[:kk]
    top = top[np.lexsort((top, -full[top]))]
    return top, full[top], len(full)


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
    """One answer against the reference's cosine of every document. The
    served ``_score`` of this system's ``knn`` section is the RAW cosine
    (Elasticsearch's own is ``(1 + cos) / 2``, the same order at half the
    distance): gaps are absolute differences of cosines.

    ``score_gap``  max |served score − reference cosine of that document|
    ``rank_gap``   reference's k-th best cosine − the worst reference
                   cosine among the served documents; 0 where the served
                   set is a top-k
    ``order_wrong`` adjacent hits whose served score rises
    ``hits_wrong`` count ≠ k, a repeated or an out-of-range id
    Without a limit (observations): ``ties_not_by_id`` (PERF.md section 7,
    fault 1), ``hits_total`` (``hits.total`` as served: this system counts
    every live document with a vector, the source's approximate search the
    candidates it gathered)."""
    want = min(int(params["k"]), int(params["size"]), len(ref_scores))
    out = {"score_gap": 0.0, "rank_gap": 0.0, "order_wrong": 0,
           "hits_wrong": int(len(ids) != want
                             or len(set(ids.tolist())) != len(ids)),
           "ties_not_by_id": 0, "hits_total": int(total)}
    if len(ids) == 0:
        return out
    if ids.min() < 0 or ids.max() >= len(ref_scores):
        out["hits_wrong"] = 1
        return out
    out["order_wrong"] = int((scores[:-1] < scores[1:]).sum())
    out["ties_not_by_id"] = int(((scores[:-1] == scores[1:])
                                 & (ids[:-1] > ids[1:])).sum())
    mine = ref_scores[ids]
    out["score_gap"] = float(np.max(np.abs(scores - mine)))
    kth = float(np.partition(ref_scores, -want)[-want])
    out["rank_gap"] = float(max(0.0, kth - float(mine.min())))
    return out
