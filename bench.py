#!/usr/bin/env python
"""Benchmark: MS-MARCO-shaped BM25 top-1000, QPS per chip.

The driver-defined headline metric (BASELINE.json): batched BM25 top-k over
a passage-scale corpus on one chip, vs a CPU lexical-engine baseline.

Two numbers are measured and the ENGINE one is the headline:
* engine — the production path: corpus installed into an Engine via the
  bulk columnar ingest (Segment.from_packed_text + install_segment), then
  ShardSearcher.query_phase_batch → jit_exec vmapped fused programs, with
  doc-id-level recall parity against CPU scoring for every query of the
  first batch.
* kernel — the standalone models/bm25.bm25_topk_batch program (the upper
  bound the engine is converging to).

Corpus: synthetic Zipf corpus shaped like MS-MARCO passages (default 200k
docs — overridable via BENCH_DOCS — ~56 tokens/doc, 30k vocab). Queries:
4-term Zipf-sampled batches (BENCH_BATCH, default 64).

CPU baseline: scipy CSR eager-impact scoring (the BM25S formulation,
PAPERS.md — generally *faster* than Lucene's postings iteration, so the
ratio is conservative) + argpartition top-k.

Prints exactly ONE JSON line:
  {"metric": ..., "value": QPS, "unit": "qps", "vs_baseline": ratio}
Everything else goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def lat_pcts(ms) -> dict:
    """The one latency-summary discipline: p50 AND the tail (p99/p999)
    of a sample array in ms. Every leg that stamps latencies uses these
    keys, so the tail_tolerance leg's numbers have comparable baselines
    across the artifact. (p999 at small n degenerates toward the max —
    still stamped, honestly near-max.)"""
    arr = np.asarray(ms, dtype=np.float64)
    return {"p50_ms": round(float(np.percentile(arr, 50)), 2),
            "p99_ms": round(float(np.percentile(arr, 99)), 2),
            "p999_ms": round(float(np.percentile(arr, 99.9)), 2)}


def program_costs_snapshot(lane_filter=None, top: int = 8) -> dict:
    """The program cost observatory's leg record: per-lane rollups
    (aggregated over every attributed node table) plus the hottest
    programs, each carrying predicted vs measured µs, the accuracy
    ratio and the roofline regime — the per-(lane, shape) cost table
    the BENCH_r06 chip capture stamps next to its latency figures."""
    from elasticsearch_tpu.observability import costs as _costs
    lanes_agg: dict = {}
    rows: list = []
    for nid in (_costs.node_ids() or [""]):
        for lane, ent in _costs.lane_rollup(nid).items():
            if lane_filter is not None and lane not in lane_filter:
                continue
            agg = lanes_agg.setdefault(lane, dict(ent))
            if agg is not ent:
                for key in ("resident", "compiles", "compile_ms",
                            "dispatches", "device_time_us", "requests",
                            "rows"):
                    agg[key] += ent[key]
        rows.extend(r for r in _costs.top_programs(nid, n=top)
                    if lane_filter is None or r["lane"] in lane_filter)
    rows.sort(key=lambda r: -r["device_time_us"])
    return {"lanes": lanes_agg, "top": rows[:top]}


def program_cost_floor_ms(lane_filter=None):
    """The cost table's measured dispatch floor (min EWMA over
    dispatched programs, ms) — cross-checked against the span-derived
    ``rtt_floor_ms_spans``: two independent books measuring the same
    device round trips must agree to a small factor."""
    from elasticsearch_tpu.observability import costs as _costs
    floors = [rec.ewma_us / 1e3
              for nid in (_costs.node_ids() or [""])
              for rec in _costs.table(nid).records()
              if rec.dispatches > 0 and
              (lane_filter is None or rec.lane in lane_filter)]
    return round(min(floors), 3) if floors else None


def timed_throughput(run, batches, n_threads: int = 1):
    """The one measurement discipline for every engine-path config: one
    warm run (the compile-cache hit), then either the full batch list
    or — when a single batch already takes >= 2 s — just one, dispatched
    concurrently when n_threads > 1 (the node's search-pool shape, which
    overlaps host-side planning and result fetches with device work).
    Returns (qps, ms_per_batch). Every config number in the JSON record
    must come through here so cross-config comparisons share the gate."""
    t0 = time.perf_counter()
    run(batches[0])
    per = time.perf_counter() - t0
    todo = len(batches) if per < 2.0 else 1
    t0 = time.perf_counter()
    if n_threads > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(n_threads) as pool:
            list(pool.map(run, batches[:todo]))
    else:
        for b in batches[:todo]:
            run(b)
    dt = time.perf_counter() - t0
    done = sum(len(b) for b in batches[:todo])
    return done / dt, dt / todo * 1e3


def ids_match_with_tolerance(got, want, label) -> bool:
    """The one id-order parity discipline for mesh-plane configs: exact
    order, or — because dd (f32 hi, lo) sort keys carry ~49-bit
    mantissas vs the oracle's f64, so colliding keys may reorder at the
    top-k boundary — a >= 0.999 set overlap, logged either way."""
    if list(got) == list(want):
        return True
    overlap = len(set(got) & set(want)) / max(len(want), 1)
    if overlap < 0.999:
        log(f"[bench] {label} parity FAIL: id overlap {overlap:.4f}")
        return False
    log(f"[bench] {label} parity: id-order differs, "
        f"set overlap {overlap:.4f}")
    return True


_ZIPF_CDF = None


def make_corpus(rng, n_docs: int, vocab: int, mean_len: int, max_unique: int,
                chunk: int = 1_000_000, realistic: bool = False):
    """Vectorized Zipf corpus directly in packed column form (chunked: the
    f64 sampling scratch for 8.8M docs would need ~8 GB at once).

    `realistic=True` (BENCH_CORPUS=msmarco) matches MS-MARCO passage
    statistics instead of the toy distribution: ~500k effective vocab,
    log-normal doc lengths (median ~50, long tail to 224), and a flatter
    Zipf exponent so query terms hit realistic df ranges."""
    if realistic:
        lens = np.clip(rng.lognormal(np.log(50.0), 0.45, n_docs),
                       10, 224).astype(np.int32)
    else:
        lens = np.clip(rng.poisson(mean_len, n_docs), 8,
                       112).astype(np.int32)
    L = int(lens.max())
    U = max_unique
    toks = np.full((n_docs, L), -1, np.int32)
    uterms = np.full((n_docs, U), -1, np.int32)
    utf = np.zeros((n_docs, U), np.float32)
    df = np.zeros(vocab, np.int64)
    for lo in range(0, n_docs, chunk):
        hi = min(lo + chunk, n_docs)
        n = hi - lo
        if realistic:
            # bounded Zipf via inverse CDF (P(rank) ∝ rank^-1.07 over
            # [1, vocab), the exponent measured on MS-MARCO passage term
            # frequencies): the top term carries ~7% of tokens (like
            # "the" in English), mid ranks carry real weight, and NO
            # probability mass collapses onto a clamp artifact (an
            # unbounded zipf draw clamped to vocab-1 would pile ~37% of
            # tokens onto one fake mega-term)
            global _ZIPF_CDF
            if _ZIPF_CDF is None or len(_ZIPF_CDF) != vocab - 1:
                w = np.arange(1, vocab, dtype=np.float64) ** -1.07
                _ZIPF_CDF = np.cumsum(w / w.sum())
            tk = (np.searchsorted(_ZIPF_CDF, rng.random((n, L)))
                  + 1).astype(np.int32)
        else:
            # zipf-ish: sample from a power-law over the vocab
            ranks = (rng.pareto(1.1, size=(n, L)) + 1)
            tk = np.minimum((ranks * 3).astype(np.int64),
                            vocab - 1).astype(np.int32)
            del ranks
        mask = np.arange(L)[None, :] < lens[lo:hi, None]
        tk = np.where(mask, tk, -1)
        toks[lo:hi] = tk

        # unique terms + counts per row (vectorized)
        order = np.argsort(tk, axis=1, kind="stable")
        st = np.take_along_axis(tk, order, axis=1)
        new = np.ones_like(st, dtype=bool)
        new[:, 1:] = st[:, 1:] != st[:, :-1]
        new &= st >= 0
        uidx = np.cumsum(new, axis=1) - 1          # unique slot per token
        rows = np.broadcast_to(np.arange(lo, hi)[:, None], (n, L))
        valid = (st >= 0) & (uidx < U)
        np.add.at(utf, (rows[valid], uidx[valid]), 1.0)
        first = new & valid
        uterms[rows[first], uidx[first]] = st[first]
        np.add.at(df, uterms[lo:hi][uterms[lo:hi] >= 0], 1)
    # trim the unique-term axis to what the corpus actually used
    used = int(np.argmax((uterms >= 0).any(axis=0)[::-1]))
    u_eff = U - used if (uterms >= 0).any() else 1
    return uterms[:, :u_eff], utf[:, :u_eff], lens, df, toks


def make_queries(rng, n_queries: int, vocab: int, terms: int, df):
    """Query terms sampled from the corpus distribution (common + rare mix)."""
    present = np.nonzero(df > 0)[0]
    w = df[present].astype(np.float64)
    w /= w.sum()
    qtids = rng.choice(present, size=(n_queries, terms), p=w).astype(np.int32)
    return qtids


def main() -> int:
    n_docs = int(os.environ.get("BENCH_DOCS", 1_000_000))
    vocab = int(os.environ.get("BENCH_VOCAB", 30_000))
    n_queries = int(os.environ.get("BENCH_QUERIES", 512))
    batch = int(os.environ.get("BENCH_BATCH", 64))
    k = int(os.environ.get("BENCH_K", 1000))
    terms = int(os.environ.get("BENCH_TERMS", 4))
    max_unique = int(os.environ.get("BENCH_MAX_UNIQUE", 80))
    corpus_mode = os.environ.get("BENCH_CORPUS", "zipf")
    if corpus_mode == "msmarco":
        vocab = int(os.environ.get("BENCH_VOCAB", 500_000))
        # = the max doc length: the unique-term cap must never truncate,
        # or the engine indexes fewer terms than the oracle scores and
        # the recall gate fails spuriously on correct results
        max_unique = int(os.environ.get("BENCH_MAX_UNIQUE", 224))

    # the default backend, or fail: the only way onto the CPU is an
    # explicit BENCH_PLATFORM=cpu (a correctness run, never a speed)
    on_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    if on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from elasticsearch_tpu.common.device import ensure_compile_cache
    from elasticsearch_tpu.models.bm25 import bm25_topk_batch
    from elasticsearch_tpu.ops.similarity import BM25Params

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not on_cpu:
        log(f"[bench] no TPU: the default backend is {dev.platform} "
            f"({dev.device_kind}). Set BENCH_PLATFORM=cpu to run on the "
            f"CPU on purpose.")
        return 2
    ensure_compile_cache()
    log(f"[bench] device: {dev.platform} ({dev})  corpus={n_docs} docs, "
        f"vocab={vocab}, k={k}, batch={batch}")

    # telemetry baseline: one ring snapshot of the process-wide counters
    # before any leg runs, so the end-of-run stamp reads honest windowed
    # rates (delta over the whole run) instead of an empty window
    from elasticsearch_tpu.observability import timeseries as _ts
    _ts.tick("", force=True)

    rng = np.random.default_rng(1234)
    t0 = time.perf_counter()
    uterms, utf, lens, df, toks = make_corpus(
        rng, n_docs, vocab, 56, max_unique,
        realistic=(corpus_mode == "msmarco"))
    avgdl = float(lens.sum()) / n_docs
    log(f"[bench] corpus built in {time.perf_counter()-t0:.1f}s  "
        f"mode={corpus_mode} avgdl={avgdl:.1f} U={uterms.shape[1]} "
        f"effective_vocab={int((df > 0).sum())}")

    qtids_all = make_queries(rng, n_queries, vocab, terms, df)
    p = BM25Params()
    idf_table = np.where(
        df > 0, np.log1p((n_docs - df + 0.5) / (df + 0.5)), 0.0
    ).astype(np.float32)
    qidf_all = idf_table[qtids_all]

    # ---- CPU baseline: BM25S-style eager CSR impact scoring ---------------
    cpu_queries = min(n_queries, int(os.environ.get("BENCH_CPU_QUERIES", 64)))
    from scipy import sparse
    valid = uterms >= 0
    rows = np.repeat(np.arange(n_docs), uterms.shape[1]).reshape(uterms.shape)
    norm = p.k1 * (1 - p.b + p.b * lens.astype(np.float64) / avgdl)
    impact = (utf * (p.k1 + 1) / (utf + norm[:, None])).astype(np.float32)
    mat = sparse.csc_matrix(
        (impact[valid], (rows[valid], uterms[valid])),
        shape=(n_docs, vocab))
    t0 = time.perf_counter()
    for qi in range(cpu_queries):
        scores = np.zeros(n_docs, np.float32)
        for t, w in zip(qtids_all[qi], qidf_all[qi]):
            col = mat.getcol(int(t))
            scores[col.indices] += w * col.data
        top = np.argpartition(scores, -k)[-k:] if n_docs > k else \
            np.arange(n_docs)
        top[np.argsort(-scores[top], kind="stable")]
    cpu_time = time.perf_counter() - t0
    cpu_qps = cpu_queries / cpu_time
    log(f"[bench] CPU baseline: {cpu_qps:.1f} QPS "
        f"({cpu_time*1000/cpu_queries:.2f} ms/query)")

    # ---- device run --------------------------------------------------------
    kernels = os.environ.get("BENCH_KERNEL", "forward").split(",")
    # the slots kernel needs power-of-2 block-divisible rows; the forward
    # kernel (the winner — see ROOFLINE.md) only needs lane alignment, so
    # pad to 8192 and save up to 2x HBM + compute at large corpora
    if set(kernels) - {"forward"}:
        n_pad = 1 << (n_docs - 1).bit_length()
    else:
        n_pad = ((n_docs + 8191) // 8192) * 8192
    if n_pad != n_docs:
        pad = n_pad - n_docs
        uterms = np.pad(uterms, ((0, pad), (0, 0)), constant_values=-1)
        utf = np.pad(utf, ((0, pad), (0, 0)))
        lens_p = np.pad(lens, (0, pad), constant_values=1)
    else:
        lens_p = lens
    live_np = np.zeros(n_pad, bool)
    live_np[:n_docs] = True

    d_uterms = jax.device_put(jnp.asarray(uterms), dev)
    d_utf = jax.device_put(jnp.asarray(utf), dev)
    d_len = jax.device_put(jnp.asarray(lens_p), dev)
    d_live = jax.device_put(jnp.asarray(live_np), dev)

    from elasticsearch_tpu.ops import postings as postings_ops

    n_batches = max(n_queries // batch, 1)
    csr_index = None
    if "csr" in kernels:
        t0 = time.perf_counter()
        csr_index = postings_ops.PostingsIndex.from_forward(
            uterms[:n_docs], utf[:n_docs], vocab)
        log(f"[bench] CSR inversion built in {time.perf_counter()-t0:.1f}s "
            f"(nnz={csr_index.docs.shape[0]})")

    # fixed shapes across batches so the timed loop hits ONE compiled
    # program per kernel (batch-dependent S/E padding would otherwise
    # recompile inside the timing window and record compile as throughput)
    s_fixed = ((batch * terms + 31) // 32) * 32
    plans = [postings_ops.plan_batch(qtids_all[i*batch:(i+1)*batch],
                                     qidf_all[i*batch:(i+1)*batch],
                                     vocab, s_total=s_fixed)
             for i in range(n_batches)]
    csr_gathers = None
    if "csr" in kernels and csr_index is not None:
        raw = [csr_index.gather_batch(t_, s_fixed, pad_to=1)
               for t_, _ in plans]
        e_fixed = max(es.shape[0] for es, _, _ in raw)
        csr_gathers = [(np.pad(es, (0, e_fixed - es.shape[0]),
                               constant_values=s_fixed),
                        np.pad(ed, (0, e_fixed - ed.shape[0])),
                        np.pad(etf, (0, e_fixed - etf.shape[0])))
                       for es, ed, etf in raw]
        log(f"[bench] csr batch entries padded to E={e_fixed}")

    def make_runner(kernel: str):
        """→ per-batch callable(i) → (scores, docs) device arrays."""
        if kernel == "forward":
            return lambda i: bm25_topk_batch(
                d_uterms, d_utf, d_len, d_live,
                jax.device_put(jnp.asarray(qtids_all[i*batch:(i+1)*batch]), dev),
                jax.device_put(jnp.asarray(qidf_all[i*batch:(i+1)*batch]), dev),
                np.float32(avgdl), k, p.k1, p.b)
        if kernel == "slots":
            def run(i):
                table, w = plans[i]
                return postings_ops.bm25_topk_batch_slots(
                    d_uterms, d_utf, d_len, d_live,
                    jax.device_put(jnp.asarray(table), dev),
                    jax.device_put(jnp.asarray(w), dev),
                    np.float32(avgdl), k, p.k1, p.b)
            return run
        if kernel == "csr":
            def run(i):
                es, ed, etf = csr_gathers[i]
                wp = np.pad(plans[i][1], ((0, 0), (0, 1)))  # zero pad slot
                return postings_ops.bm25_topk_batch_csr(
                    jax.device_put(jnp.asarray(es), dev),
                    jax.device_put(jnp.asarray(ed), dev),
                    jax.device_put(jnp.asarray(etf), dev),
                    d_len, d_live,
                    jax.device_put(jnp.asarray(wp), dev),
                    np.float32(avgdl), n_pad, k, p.k1, p.b)
            return run
        raise ValueError(f"unknown kernel [{kernel}]")

    results = {}
    outs0 = {}
    for kernel in kernels:
        run_batch = make_runner(kernel)
        t0 = time.perf_counter()
        s, d = run_batch(0)
        s.block_until_ready()
        compile_s = time.perf_counter() - t0
        outs0[kernel] = (np.asarray(s), np.asarray(d))
        # steady-state: time one batch; adaptively decide how many to run
        t0 = time.perf_counter()
        s, d = run_batch(0)
        s.block_until_ready()
        per_batch = time.perf_counter() - t0
        todo = n_batches if per_batch < 2.0 else 1
        t0 = time.perf_counter()
        last = None
        for i in range(todo):
            last = run_batch(i)
        last[0].block_until_ready()
        dt = time.perf_counter() - t0
        qps = (todo * batch) / dt
        results[kernel] = {"qps": round(qps, 2),
                           "ms_per_batch": round(dt / todo * 1000, 2),
                           "compile_s": round(compile_s, 1)}
        log(f"[bench] kernel={kernel}: {qps:.1f} QPS "
            f"({dt/todo*1000:.1f} ms / {batch}-query batch, "
            f"compile {compile_s:.1f}s)")

    best = max(results, key=lambda kr: results[kr]["qps"])
    kernel_qps = results[best]["qps"]
    log(f"[bench] best kernel: {best}")

    # ---- recall parity: doc-id-level, every query of batch 0 ---------------
    def cpu_ref_scores(qi):
        scores = np.zeros(n_docs, np.float32)
        for t, w in zip(qtids_all[qi], qidf_all[qi]):
            col = mat.getcol(int(t))
            scores[col.indices] += w * col.data
        return scores

    def parity(rows, label):
        """rows: per query (doc_ids, scores) with -1-padding allowed.
        Checks (a) each returned doc's score equals the CPU score of THAT
        doc id, (b) the returned set is a true top-k (k-th score matches
        the CPU k-th best)."""
        for qi, (d_row, s_row) in enumerate(rows):
            ref = cpu_ref_scores(qi)
            valid = d_row >= 0
            dv = d_row[valid].astype(np.int64)
            sv = s_row[valid]
            if (dv >= n_docs).any():
                log(f"[bench] {label} parity FAIL q{qi}: padded-doc id")
                return False
            if not np.allclose(ref[dv], sv, rtol=2e-4, atol=1e-4):
                bad = np.argmax(np.abs(ref[dv] - sv))
                log(f"[bench] {label} parity FAIL q{qi}: doc {dv[bad]} "
                    f"got {sv[bad]:.5f} want {ref[dv[bad]]:.5f}")
                return False
            kk = min(k, int((ref > 0).sum()))
            if sv.shape[0] < kk:
                log(f"[bench] {label} parity FAIL q{qi}: returned "
                    f"{sv.shape[0]} docs, CPU found {kk} matches")
                return False
            ref_top = np.sort(ref)[::-1][:kk]
            if not np.allclose(np.sort(sv)[::-1][:kk], ref_top,
                               rtol=2e-4, atol=1e-4):
                log(f"[bench] {label} parity FAIL q{qi}: not the true top-k")
                return False
        return True

    s0, d0 = outs0[best]
    kernel_ok = parity([(d0[i], s0[i]) for i in range(batch)], best)
    log(f"[bench] kernel recall parity ({batch} queries, doc-id level): "
        f"{kernel_ok}")

    # ---- engine path: the product (ShardSearcher.query_phase → jit_exec) ---
    engine = {}
    engine_ok = True
    if os.environ.get("BENCH_ENGINE", "1") != "0":
        import tempfile
        from pathlib import Path
        from concurrent.futures import ThreadPoolExecutor
        from elasticsearch_tpu.index.segment import Segment
        from elasticsearch_tpu.index.engine import Engine
        from elasticsearch_tpu.index.device_reader import device_reader_for
        from elasticsearch_tpu.mapping import MapperService
        from elasticsearch_tpu.search.phase import (ShardSearcher,
                                                    parse_search_request)

        # release the standalone kernel's device arrays first: at MS-MARCO
        # scale the engine's reader needs the HBM they occupy
        import gc
        del d_uterms, d_utf, d_len, d_live, run_batch
        gc.collect()

        w = len(str(vocab - 1))
        term_names = [f"t{i:0{w}d}" for i in range(vocab)]
        t0 = time.perf_counter()
        vec_dims = int(os.environ.get("BENCH_VECTOR_DIMS", 768))
        ms_map = MapperService()
        ms_map.merge("_doc", {"properties": {
            "body": {"type": "text", "analyzer": "whitespace"},
            "rank": {"type": "double"},
            "cat": {"type": "keyword"},
            "vec": {"type": "dense_vector", "dims": max(vec_dims, 1)}}})
        eng = Engine(Path(tempfile.mkdtemp(prefix="bench_engine_")), ms_map)
        # install as power-of-2-bucketed segments of <=2^20 rows — the
        # engine's own segment discipline (doc_count_bucket): per-segment
        # program intermediates stay ~[B, 1M] instead of [B, corpus], and
        # the cross-segment device merge stitches the shard top-k
        seg_rows = int(os.environ.get("BENCH_SEG_ROWS", 1 << 20))
        # positions cost ~40% of HBM and BM25 doesn't read them; keep them
        # at small scale (phrase parity elsewhere), drop them when the
        # corpus wouldn't fit (index_options: freqs analog)
        with_positions = os.environ.get(
            "BENCH_POSITIONS",
            "1" if n_docs <= 2_000_000 else "0") == "1"
        from elasticsearch_tpu.index.segment import (
            KeywordFieldColumn, NumericFieldColumn, VectorFieldColumn,
            doc_count_bucket)
        # BASELINE configs 3/4 need doc-values + vector columns: a numeric
        # "rank" everywhere; unit vectors only while they fit HBM
        with_vectors = os.environ.get(
            "BENCH_VECTORS",
            "1" if n_docs <= 1_200_000 else "0") == "1" and vec_dims > 0
        rank_all = rng.random(n_docs).astype(np.float64) * 100.0
        # keyword category column — the generalized-plane bench sorts by
        # rank and reduces a terms agg over this in-program. Drawn from a
        # CHILD generator: inserting a draw into the shared stream would
        # silently change every later seeded draw (vectors, queries)
        # and break cross-commit comparability of recorded numbers.
        cat_names = [f"cat{i:02d}" for i in range(16)]
        cat_all = np.random.default_rng(4242).integers(
            0, 16, n_docs).astype(np.int32)
        n_segs = -(-n_docs // seg_rows)
        for lo in range(0, n_docs, seg_rows):
            hi = min(lo + seg_rows, n_docs)
            rows = hi - lo
            np_rows = doc_count_bucket(rows)
            def padrows(a, fill):
                out_shape = (np_rows,) + a.shape[1:]
                out = np.full(out_shape, fill, a.dtype)
                out[:rows] = a[lo:hi]
                return out
            seg_df = np.zeros(vocab, np.int64)
            seg_ut = uterms[lo:hi]
            np.add.at(seg_df, seg_ut[seg_ut >= 0], 1)
            seg = Segment.from_packed_text(
                0, "body", terms=term_names,
                tokens=padrows(toks, -1) if with_positions else None,
                uterms=padrows(uterms, -1), utf=padrows(utf, 0.0),
                doc_len=padrows(lens, 0), df=seg_df, num_docs=rows,
                ids=[str(lo + i) for i in range(rows)] +
                    [""] * (np_rows - rows))
            exists = np.zeros(np_rows, bool)
            exists[:rows] = True
            seg.numeric_fields["rank"] = NumericFieldColumn(
                values=padrows(rank_all, 0.0), exists=exists.copy())
            if with_vectors:
                vecs = np.zeros((np_rows, vec_dims), np.float32)
                raw = rng.standard_normal((rows, vec_dims)).astype(np.float32)
                vecs[:rows] = raw / np.linalg.norm(raw, axis=1,
                                                   keepdims=True)
                seg.vector_fields["vec"] = VectorFieldColumn(
                    vecs=vecs, exists=exists.copy(), dims=vec_dims)
            eng.install_segment(seg, track_versions=False)
        searcher = ShardSearcher(0, device_reader_for(eng, device=dev),
                                 ms_map)
        log(f"[bench] engine: {n_segs} segment(s) installed + "
            f"device-packed in {time.perf_counter() - t0:.1f}s "
            f"(positions={'yes' if with_positions else 'no'})")
        # reader-global doc id → corpus row (padding rows map to -1)
        gid_to_orig = np.full(searcher.reader.max_doc, -1, np.int64)
        for dseg in searcher.reader.segments:
            n_real = dseg.seg.num_docs
            base = dseg.doc_base
            first_id = int(dseg.seg.ids[0])
            gid_to_orig[base:base + n_real] = np.arange(
                first_id, first_id + n_real)

        texts = [" ".join(term_names[t] for t in row) for row in qtids_all]
        reqs = [parse_search_request({"query": {"match": {"body": tx}},
                                      "size": k}) for tx in texts]
        bs = [reqs[i * batch:(i + 1) * batch] for i in range(n_batches)]

        t0 = time.perf_counter()
        res0 = searcher.query_phase_batch(bs[0])
        compile_s = time.perf_counter() - t0
        assert res0 is not None, "engine batch path fell back"
        engine_rows = []
        for r in res0:
            orig = gid_to_orig[np.asarray(r.doc_ids, np.int64)]
            assert (orig >= 0).all(), "engine returned a padding row"
            engine_rows.append((orig, np.asarray(r.scores)))
        engine_ok = parity(engine_rows, "engine")
        log(f"[bench] engine recall parity ({batch} queries, doc-id level): "
            f"{engine_ok}")

        # ---- independent Lucene-BM25 oracle (VERDICT r3 #6) -----------
        # a from-first-principles scorer (scripts/bm25_oracle.py) that
        # shares no code with the engine or the CPU baseline validates
        # BM25 semantics — idf, length norm, tie behavior — not just
        # internal consistency. Skipped above 2M docs (oracle memory).
        oracle_recall = None
        if os.environ.get("BENCH_ORACLE", "1") == "1" and \
                n_docs <= 2_000_000:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "scripts"))
            from bm25_oracle import (
                BM25Oracle, recall_with_tie_tolerance)
            t0 = time.perf_counter()
            oracle = BM25Oracle(toks)
            recs = []
            for qi in range(len(engine_rows)):
                sc = oracle.score_query(qtids_all[qi])
                ids, _ = oracle.topk(qtids_all[qi], k, scores=sc)
                recs.append(recall_with_tie_tolerance(
                    ids, sc, engine_rows[qi][0], k))
            oracle_recall = float(np.mean(recs))
            log(f"[bench] independent Lucene-BM25 oracle recall@{k}: "
                f"{oracle_recall:.4f} "
                f"({time.perf_counter() - t0:.1f}s, "
                f"{len(engine_rows)} queries)")

        # 8 in-flight batches: concurrent requests (the node's search
        # pool) overlap one batch's blocking device→host fetch with the
        # next batch's host planning and device work
        n_threads = int(os.environ.get("BENCH_ENGINE_THREADS", 8))
        engine_qps, ms_b = timed_throughput(
            searcher.query_phase_batch, bs, n_threads)
        log(f"[bench] engine (batched x{batch}, {n_threads} threads): "
            f"{engine_qps:.1f} QPS ({ms_b:.1f} ms/batch, "
            f"compile {compile_s:.1f}s)")

        # ---- BASELINE configs 2-4 on the engine path --------------------
        # (2: bool multi-term + phrase; 3: function_score
        # field_value_factor; 4: brute-force cosine kNN). Config 1 is the
        # headline above; config 5's scatter-gather+merge is exercised by
        # the per-segment fan-out + device merge here and by the
        # multi-shard tests/mesh dryrun (no standalone number yet).
        configs = {}
        if os.environ.get("BENCH_CONFIGS", "1") != "0":
            def measure(name, bodies):
                breqs = [parse_search_request(b) for b in bodies]
                cbs = [breqs[i:i + batch]
                       for i in range(0, len(breqs), batch)] or [[]]
                r0 = searcher.query_phase_batch(cbs[0])
                assert r0 is not None, f"config {name} fell back"
                qps_c, ms_c = timed_throughput(
                    searcher.query_phase_batch, cbs, n_threads)
                configs[name] = {"qps": round(qps_c, 2),
                                 "ms_per_batch": round(ms_c, 2)}
                log(f"[bench] config {name}: {configs[name]['qps']} QPS")

            ncq = min(n_queries, batch * 4)
            # config 2: 2-term must + 2-term phrase (real adjacent pairs)
            if with_positions:
                bodies = []
                for qi in range(ncq):
                    t1, t2 = qtids_all[qi][0], qtids_all[qi][1]
                    d = int(rng.integers(0, n_docs))
                    # NOT `p` — that name is the run-wide BM25Params,
                    # which the impact leg reads as p.k1 much later
                    pos = int(rng.integers(0, max(int(lens[d]) - 1, 1)))
                    a, b_ = int(toks[d, pos]), int(toks[d, pos + 1])
                    if a < 0 or b_ < 0:
                        a, b_ = int(toks[d, 0]), int(toks[d, 1])
                    bodies.append({"query": {"bool": {
                        "must": [{"match": {
                            "body": f"{term_names[t1]} {term_names[t2]}"}}],
                        "should": [{"match_phrase": {
                            "body": f"{term_names[a]} {term_names[b_]}"}}],
                    }}, "size": k})
                measure("bool_phrase", bodies)
            # config 3: function_score field_value_factor over the match
            bodies = [{"query": {"function_score": {
                "query": {"match": {"body": texts[qi]}},
                "functions": [{"field_value_factor": {
                    "field": "rank", "modifier": "log1p", "factor": 1.0}}],
                "boost_mode": "multiply"}}, "size": k}
                for qi in range(ncq)]
            measure("function_score", bodies)
            # config 4: brute-force cosine kNN over unit vectors —
            # served by the TOP-LEVEL `knn` section (the dedicated
            # vector lane with candidate oversampling; the query-DSL
            # `knn` leaf remains as a back-compat alias, parity-pinned
            # in tests/test_knn_hybrid.py)
            if with_vectors:
                qvecs = rng.standard_normal(
                    (ncq, vec_dims)).astype(np.float32)
                qvecs /= np.linalg.norm(qvecs, axis=1, keepdims=True)
                kc = min(k, 100)
                bodies = [{"knn": {
                    "field": "vec", "query_vector": qvecs[qi].tolist(),
                    "k": kc, "num_candidates": max(kc, 100)},
                    "size": kc} for qi in range(ncq)]
                measure("dense_cosine", bodies)

        # ---- rag_hybrid leg: msearch-heavy hybrid (BM25+kNN RRF) ------
        # retrieval under 16/32 concurrent clients — the RAG workload
        # (PAPERS.md, Elasticsearch-RAG): every request carries BOTH a
        # lexical clause and a knn section, fused IN-PROGRAM via RRF so
        # each is one device dispatch. Stamps QPS, fusion-dispatch /
        # admission counters (reconciled against the request count),
        # and int8-vs-f32 recall@10 over the same resident corpus.
        rag_hybrid = {}
        if os.environ.get("BENCH_RAG", "1") != "0" and with_vectors:
            from elasticsearch_tpu.search import jit_exec as _jx
            nrq = min(n_queries, batch * 4)
            rag_rng = np.random.default_rng(777)
            rag_qv = rag_rng.standard_normal(
                (nrq, vec_dims)).astype(np.float32)
            rag_qv /= np.linalg.norm(rag_qv, axis=1, keepdims=True)
            kc = min(k, 100)
            hreqs = [parse_search_request({
                "query": {"match": {"body": texts[qi % len(texts)]}},
                "knn": {"field": "vec",
                        "query_vector": rag_qv[qi].tolist(),
                        "k": kc, "num_candidates": max(kc, 100)},
                "size": kc}) for qi in range(nrq)]
            hbs = [hreqs[i:i + batch]
                   for i in range(0, len(hreqs), batch)] or [[]]
            t0 = time.perf_counter()
            r0 = searcher.query_phase_batch(hbs[0])
            rag_compile_s = time.perf_counter() - t0
            assert r0 is not None, "rag_hybrid batch fell back"
            # the concurrent rounds drive the LIVE continuous-batching
            # scheduler with request-at-a-time hybrid clients (the
            # production shape — msearch batches already ride
            # query_phase_batch directly): per-round fusion/admission
            # counters must reconcile against the request count, pad
            # rows excluded by construction (n_real)
            from collections import Counter as _RagCounter

            from elasticsearch_tpu.search.scheduler import (
                ContinuousBatchScheduler as _RagSched, classify as _rcls)
            rag_shapes = [_rcls(r, searcher) for r in hreqs]
            rag_dom = _RagCounter(
                sh for ln, sh in rag_shapes
                if ln == "knn").most_common(1)[0][0]
            rag_reqs = [r for r, (ln, sh) in zip(hreqs, rag_shapes)
                        if ln == "knn" and sh == rag_dom]
            rag_clients = {}
            for nclients in (16, 32):
                mb = max(nclients // 4, 4)
                b_ = 1
                while b_ <= mb:          # warm the family's pow2 buckets
                    searcher.query_phase_batch([rag_reqs[0]] * b_)
                    b_ = b_ * 2 if b_ < mb else mb + 1
                sched_r = _RagSched(node_id="bench-rag", max_batch=mb,
                                    max_in_flight=6)
                per_client = max(len(rag_reqs) // nclients, 2)
                done = [0]
                rag_lock = threading.Lock()

                def rag_client(ci: int) -> None:
                    for qi in range(per_client):
                        r = rag_reqs[(ci * per_client + qi)
                                     % len(rag_reqs)]
                        out = sched_r.execute(
                            "knn", ("knn", rag_dom), r,
                            searcher.query_phase_batch_launch,
                            searcher.query_phase_batch_drain)
                        if out is None:
                            searcher.query_phase(r)
                        with rag_lock:
                            done[0] += 1
                stA = _jx.cache_stats()
                t0 = time.perf_counter()
                ths = [threading.Thread(target=rag_client, args=(ci,))
                       for ci in range(nclients)]
                for th in ths:
                    th.start()
                for th in ths:
                    th.join()
                dt = time.perf_counter() - t0
                stB = _jx.cache_stats()
                st_s = sched_r.stats()
                sched_r.close()
                qps_h = done[0] / dt
                fusion_delta = stB["fusion_dispatches"] - \
                    stA["fusion_dispatches"]
                rag_clients[str(nclients)] = {
                    "qps": round(qps_h, 2),
                    "requests": done[0],
                    "fusion_dispatches": fusion_delta,
                    "counters_reconciled":
                        bool(fusion_delta == done[0]
                             and st_s["reconciled"]),
                    "scheduler": {
                        "batches_launched": st_s["batches_launched"],
                        "in_flight_high_water":
                            st_s["in_flight_high_water"],
                        "shed": st_s["shed"],
                        "pad_rows": st_s["pad_rows"],
                        "declined": st_s["declined"]}}
                log(f"[bench] rag_hybrid x{nclients} clients (live "
                    f"scheduler): {qps_h:.1f} QPS, "
                    f"{st_s['batches_launched']} batches, fusion "
                    f"reconciled={rag_clients[str(nclients)]['counters_reconciled']}")
            # int8-vs-f32 recall@10: the same reader scored through an
            # int8-quantized pack (per-segment scale/offset snapshot)
            # vs the exact f32 pack
            _jx.configure_knn_plane("bench_rag_int8",
                                    {"index.knn.quantization": "int8"})
            s8 = ShardSearcher(0, searcher.reader, ms_map,
                               index_name="bench_rag_int8")
            overlap = total_top = 0
            for qi in range(min(nrq, 32)):
                kb = {"knn": {"field": "vec",
                              "query_vector": rag_qv[qi].tolist(),
                              "k": 10, "num_candidates": 100},
                      "size": 10}
                rf = searcher.query_phase(parse_search_request(kb))
                r8 = s8.query_phase(parse_search_request(kb))
                f_ids = set(np.asarray(rf.doc_ids).tolist())
                overlap += len(
                    f_ids & set(np.asarray(r8.doc_ids).tolist()))
                total_top += len(f_ids)
            st1 = _jx.cache_stats()
            rag_hybrid = {
                "clients": rag_clients,
                "compile_s": round(rag_compile_s, 1),
                "fusion_dispatches": sum(
                    rc["fusion_dispatches"]
                    for rc in rag_clients.values()),
                "requests": sum(rc["requests"]
                                for rc in rag_clients.values()),
                "counters_reconciled": all(
                    rc["counters_reconciled"]
                    for rc in rag_clients.values()),
                "knn_fallback_reasons":
                    dict(st1.get("knn_fallback_reasons", {})),
                "int8_recall_at_10":
                    round(overlap / max(total_top, 1), 4),
            }
            log(f"[bench] rag_hybrid int8-vs-f32 recall@10: "
                f"{rag_hybrid['int8_recall_at_10']}")

        # request-at-a-time path (the reference's dispatch model,
        # QueryPhase.java:314). Three measurements tell the whole story:
        #   1. closed-loop serial p50 — one blocking client, floored by
        #      the dispatch + blocking-fetch round trip, so
        #   2. the device→host RTT floor is measured directly (a fresh
        #      4-byte fetch pays the same fixed cost as a query result), and
        #   3. concurrent request-at-a-time clients through the admission
        #      queue (search/batching.py) — the realistic server shape —
        #      show per-request p50 once micro-batching amortizes the RTT.
        nq_serial = min(batch, 32)
        searcher.query_phase(reqs[0])
        # tracer-off overhead guard: the timed serial leg below must
        # allocate ZERO span objects (observability/tracing.py contract)
        from elasticsearch_tpu.observability import tracing as obs_trace
        spans_alloc0 = obs_trace.spans_allocated()
        lat = []
        for r in reqs[:nq_serial]:
            t0 = time.perf_counter()
            searcher.query_phase(r)
            lat.append(time.perf_counter() - t0)
        lat = np.array(lat) * 1e3
        serial_p50 = float(np.percentile(lat, 50))
        serial_qps = 1e3 / (lat.mean() or 1.0)
        # RTT floor: dispatching a trivial program and fetching its FRESH
        # scalar — the irreducible per-request cost any request-response
        # loop pays on this host
        import jax as _jax
        import jax.numpy as _jnp
        _one = _jax.device_put(np.float32(1.0))
        _inc = _jax.jit(lambda a, i: a + i)
        np.asarray(_inc(_one, 0.0))
        rtts = []
        for i in range(1, 16):
            t0 = time.perf_counter()
            np.asarray(_inc(_one, float(i)))
            rtts.append(time.perf_counter() - t0)
        rtt_ms = float(np.percentile(np.array(rtts) * 1e3, 50))
        log(f"[bench] engine (request-at-a-time): {serial_qps:.1f} QPS, "
            f"p50 {serial_p50:.1f} ms (device↔host RTT floor "
            f"{rtt_ms:.1f} ms)")

        # ---- span-trace attribution leg -------------------------------
        # A few PROFILED probes attribute the serial path from spans —
        # device dispatch share, compile share, span-derived RTT floor —
        # and stamp a Chrome-trace artifact + histogram summary for the
        # leg; the off-path guard above asserts the timed leg allocated
        # no spans (tracer-off throughput within noise of untraced).
        spans_off_delta = obs_trace.spans_allocated() - spans_alloc0
        from elasticsearch_tpu.observability import chrome as obs_chrome
        from elasticsearch_tpu.observability import (
            histograms as obs_hist, use_node)
        with use_node("bench"), \
                obs_trace.trace("bench-engine", "bench"), \
                obs_trace.collect_spans() as leg_spans:
            for r in reqs[:min(nq_serial, 8)]:
                with obs_trace.span("probe"):
                    searcher.query_phase(r)
        disp_us = [s["duration_us"] for s in leg_spans
                   if s["name"] in ("dispatch", "plane-dispatch")]
        comp_us = [s["duration_us"] for s in leg_spans
                   if s["name"] == "compile"]
        probe_us = sum(s["duration_us"] for s in leg_spans
                       if s["name"] == "probe") or 1
        trace_art = {
            "spans": len(leg_spans),
            "rtt_floor_ms_spans":
                round(float(np.percentile(
                    np.array(disp_us) / 1e3, 50)), 3) if disp_us
                else None,
            "compile_share": round(sum(comp_us) / probe_us, 4),
            "device_share": round(sum(disp_us) / probe_us, 4),
            "tracer_off_spans_allocated": int(spans_off_delta),
            "overhead_ok": spans_off_delta == 0,
            "histograms": obs_hist.summaries("bench"),
        }
        # cross-check: the cost table's measured dispatch floor vs the
        # span-derived RTT floor — two independent books over the same
        # device round trips; "consistent" means within a 10x band
        # (spans time ONE dispatch+fetch, the EWMA smooths many and CPU
        # overheads differ) and both present — honest on divergence
        cost_floor = program_cost_floor_ms()
        trace_art["rtt_floor_ms_costs"] = cost_floor
        span_floor = trace_art["rtt_floor_ms_spans"]
        trace_art["rtt_floor_consistent"] = (
            bool(span_floor and cost_floor and
                 0.1 <= span_floor / cost_floor <= 10.0)
            if (span_floor and cost_floor) else None)
        trace_path = os.environ.get("BENCH_TRACE_OUT",
                                    "TRACE_engine.json")
        try:
            with open(trace_path, "w") as fh:
                json.dump(obs_chrome.chrome_trace(leg_spans), fh)
            trace_art["chrome_trace"] = trace_path
        except OSError:
            trace_art["chrome_trace"] = None
        log(f"[bench] trace leg: {trace_art['spans']} spans, "
            f"rtt_floor(spans) {trace_art['rtt_floor_ms_spans']} ms, "
            f"device share {trace_art['device_share']}, "
            f"compile share {trace_art['compile_share']}, "
            f"off-path allocations {spans_off_delta}")
        # concurrent closed-loop clients through the LIVE continuous-
        # batching scheduler (search/scheduler.py — the same class
        # SearchActions wires into every node's shard path, retiring the
        # bench's hand-built AdaptiveBatcher): each client sends one
        # query at a time and blocks for its answer. The scheduler keeps
        # one dispatch always in flight — batch N+1 launches while batch
        # N computes and batch N−1's drain rides a worker — and admission
        # is continuous (a batch is whatever queued while the window was
        # full), so closed-loop throughput approaches the batch ceiling
        # instead of N_clients / (RTT + device + formation) serialized.
        from collections import Counter as _Counter

        from elasticsearch_tpu.search.scheduler import (
            ContinuousBatchScheduler, classify)
        # one program FAMILY for the timed leg (the dominant query shape
        # among the request set): the leg measures scheduling, not
        # compiles — minority shapes would each pay a one-off trace in
        # the timed region
        req_shapes = [classify(r, searcher) for r in reqs]
        dom_shape = _Counter(sh for ln, sh in req_shapes
                             if ln is not None).most_common(1)[0][0]
        cl_reqs = [r for r, (ln, sh) in zip(reqs, req_shapes)
                   if ln is not None and sh == dom_shape]

        def run_closed_loop(n_clients: int, max_batch: int,
                            warmed: set) -> dict:
            per_client = max(nq_serial // 4, 4)
            sched = ContinuousBatchScheduler(
                node_id="bench", max_batch=max_batch, max_in_flight=6)
            # warm every pow2 bucket the scheduler can form for the
            # family, so the timed region never pays a compile
            b_ = 1
            while b_ <= max_batch:
                if (dom_shape, b_) not in warmed:
                    searcher.query_phase_batch([cl_reqs[0]] * b_)
                    warmed.add((dom_shape, b_))
                b_ = b_ * 2 if b_ < max_batch else max_batch + 1
            cl_lat: list[float] = []
            cl_lock = threading.Lock()
            serial_falls = [0]

            def client(ci: int) -> None:
                mine = []
                for qi in range(per_client):
                    r = cl_reqs[(ci * per_client + qi) % len(cl_reqs)]
                    t0 = time.perf_counter()
                    out = sched.execute(
                        "plane", ("plane", dom_shape), r,
                        searcher.query_phase_batch_launch,
                        searcher.query_phase_batch_drain)
                    if out is None:          # declined: serial path
                        searcher.query_phase(r)
                        with cl_lock:
                            serial_falls[0] += 1
                    mine.append(time.perf_counter() - t0)
                with cl_lock:
                    cl_lat.extend(mine)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(ci,))
                       for ci in range(n_clients)]
            for th in threads:
                th.start()
            # counter reconciliation AT EVERY SAMPLE while the storm
            # runs (launched == drained + in-flight; submitted ==
            # queued + in-flight + delivered + declined + shed)
            recon_samples: list[bool] = []
            while any(th.is_alive() for th in threads):
                recon_samples.append(sched.stats()["reconciled"])
                time.sleep(0.02)
            for th in threads:
                th.join()
            cl_dt = time.perf_counter() - t0
            st = sched.stats()
            sched.close()
            cl = np.array(cl_lat) * 1e3
            pcts = lat_pcts(cl)
            p50 = pcts["p50_ms"]
            qps = len(cl_lat) / cl_dt
            starvation_free = len(cl_lat) == n_clients * per_client
            log(f"[bench] engine ({n_clients} request-at-a-time clients, "
                f"live scheduler, micro-batch={max_batch}): "
                f"p50 {p50:.1f} ms, p99 {pcts['p99_ms']:.1f} ms, "
                f"{qps:.1f} QPS — {st['batches_launched']} batches, "
                f"in-flight hw {st['in_flight_high_water']}, "
                f"shed {st['shed']}, reconciled "
                f"{all(recon_samples) and st['reconciled']}")
            return {"clients": n_clients, "max_batch": max_batch,
                    **pcts, "qps": round(qps, 2),
                    "scheduler": {
                        "batches_launched": st["batches_launched"],
                        "batches_drained": st["batches_drained"],
                        "in_flight_high_water":
                            st["in_flight_high_water"],
                        "delivered": st["delivered"],
                        "declined": st["declined"],
                        "shed": st["shed"],
                        "shed_reasons": st["shed_reasons"],
                        "pad_rows": st["pad_rows"],
                        "serial_fallbacks": serial_falls[0],
                        "starvation_free": starvation_free,
                        "reconciled_at_every_sample":
                            bool(all(recon_samples) and st["reconciled"]),
                        "samples": len(recon_samples)}}

        warmed: set = set()
        n_clients = int(os.environ.get("BENCH_CLIENTS", 32))
        conc_rounds = [run_closed_loop(max(n_clients // 2, 4),
                                       max(n_clients // 4, 4), warmed),
                       run_closed_loop(n_clients,
                                       max(n_clients // 4, 4), warmed)]
        conc = max(conc_rounds, key=lambda r: r["qps"])
        conc_p50, conc_qps = conc["p50_ms"], conc["qps"]
        n_clients = conc["clients"]
        # the BENCH_r06 acceptance figure: concurrent closed-loop QPS
        # through the live scheduler vs the serial batch ceiling
        # (engine_qps above — saturated query_phase_batch throughput)
        ceiling_ratio = conc_qps / max(engine_qps, 1e-9)
        log(f"[bench] scheduler concurrent/batch-ceiling ratio: "
            f"{ceiling_ratio:.3f} ({conc_qps:.1f} / {engine_qps:.1f} "
            f"QPS, target ≥ 0.60 at 32 clients)")
        serial_pcts = lat_pcts(lat)
        engine = {"qps": round(engine_qps, 2),
                  "serial_qps": round(serial_qps, 2),
                  "serial_p50_ms": round(serial_p50, 2),
                  "serial_p99_ms": serial_pcts["p99_ms"],
                  "serial_p999_ms": serial_pcts["p999_ms"],
                  "rtt_floor_ms": round(rtt_ms, 2),
                  "oracle_recall_at_k": (round(oracle_recall, 5)
                                         if oracle_recall is not None
                                         else None),
                  # closed-loop p50 minus the measured interconnect RTT:
                  # the query work itself, i.e. the serial latency a
                  # locally-attached TPU (µs-scale D2H) would observe
                  "serial_device_ms": round(max(serial_p50 - rtt_ms, 0.0),
                                            2),
                  "concurrent": {"clients": n_clients,
                                 "p50_ms": round(conc_p50, 2),
                                 "qps": round(conc_qps, 2),
                                 "batch_ceiling_qps": round(engine_qps, 2),
                                 "ceiling_ratio": round(ceiling_ratio, 4),
                                 "ceiling_target_met":
                                     bool(ceiling_ratio >= 0.60),
                                 "scheduler": conc["scheduler"],
                                 "rounds": conc_rounds},
                  "ms_per_batch": round(ms_b, 2),
                  "threads": n_threads,
                  "compile_s": round(compile_s, 1),
                  "trace": trace_art,
                  "configs": configs,
                  "rag_hybrid": rag_hybrid,
                  # per-(lane, shape) predicted-vs-measured cost books
                  # accumulated over this leg's programs
                  "program_costs": program_costs_snapshot()}
        eng.close()

        # ---- BASELINE config 5: 8-shard query_then_fetch top-1000 ------
        # (fan-out ref: TransportSearchTypeAction.java:137; merge ref:
        # SearchPhaseController.sortDocs:165-268). Hash-partition the
        # corpus over 8 single-segment shard engines on the ONE chip, run
        # every shard's fused program per batch, then the coordinator-side
        # cross-shard top-k merge with from/size pagination. Runs after
        # the single-shard engine is closed so HBM holds one corpus copy.
        if os.environ.get("BENCH_CONFIG5", "1") == "1":
            n_shards = 8
            k5 = min(k, 1000)
            from5 = min(int(os.environ.get("BENCH_CONFIG5_FROM", 500)),
                        max(k5 - 100, 0))
            per_shard = -(-n_docs // n_shards)
            searchers5 = []
            engines5 = []
            t0 = time.perf_counter()
            for si in range(n_shards):
                lo = si * per_shard
                hi = min(lo + per_shard, n_docs)
                rows = hi - lo
                np_rows = doc_count_bucket(rows)

                def spad(a, fill):
                    out = np.full((np_rows,) + a.shape[1:], fill, a.dtype)
                    out[:rows] = a[lo:hi]
                    return out
                seg_df = np.zeros(vocab, np.int64)
                sut = uterms[lo:hi]
                np.add.at(seg_df, sut[sut >= 0], 1)
                seg = Segment.from_packed_text(
                    0, "body", terms=term_names, tokens=None,
                    uterms=spad(uterms, -1), utf=spad(utf, 0.0),
                    doc_len=spad(lens, 0), df=seg_df, num_docs=rows,
                    ids=[str(lo + i) for i in range(rows)] +
                        [""] * (np_rows - rows))
                if os.environ.get("BENCH_MESH", "1") == "1":
                    # only the generalized-plane config reads these;
                    # readers eagerly upload every column, so attaching
                    # them unconditionally would carry ~12 B/doc of
                    # never-read HBM through the RPC-only configs
                    s5_exists = np.zeros(np_rows, bool)
                    s5_exists[:rows] = True
                    seg.numeric_fields["rank"] = NumericFieldColumn(
                        values=spad(rank_all, 0.0), exists=s5_exists)
                    seg.keyword_fields["cat"] = KeywordFieldColumn(
                        vocab=list(cat_names),
                        ords=spad(cat_all[:, None], -1))
                e5 = Engine(Path(tempfile.mkdtemp(prefix="bench_s5_")),
                            ms_map)
                e5.install_segment(seg, track_versions=False)
                engines5.append(e5)
                searchers5.append(ShardSearcher(
                    si, device_reader_for(e5, device=dev), ms_map))
            log(f"[bench] config 8shard: {n_shards} shard engines packed "
                f"in {time.perf_counter() - t0:.1f}s")
            reqs5 = [parse_search_request(
                {"query": {"match": {"body": tx}}, "size": k5})
                for tx in texts[:batch * 4]]
            bs5 = [reqs5[i:i + batch] for i in range(0, len(reqs5), batch)]

            shard_pool = ThreadPoolExecutor(n_shards)

            def run_batch5(breqs):
                # scatter: one fused program per shard, dispatched
                # CONCURRENTLY — the device serializes the programs but
                # the per-shard top-k fetch round trips overlap (the node
                # fans shard requests out in parallel the same way)
                per_shard_res = list(shard_pool.map(
                    lambda s5: s5.query_phase_batch(breqs), searchers5))
                # gather + reduce: cross-shard merged top-k, then the
                # from/size page slice (sortDocs + pagination)
                out_pages = []
                for qi in range(len(breqs)):
                    scores = np.concatenate([
                        np.asarray(r[qi].scores)
                        for r in per_shard_res])
                    gids = np.concatenate([
                        np.asarray(r[qi].doc_ids, np.int64)
                        + si * per_shard
                        for si, r in enumerate(per_shard_res)])
                    top = min(k5, scores.size)
                    sel = np.argpartition(-scores, top - 1)[:top]
                    order = sel[np.argsort(-scores[sel], kind="stable")]
                    page = order[from5:from5 + 100]
                    out_pages.append(gids[page])
                return out_pages
            first = run_batch5(bs5[0])
            assert all(len(p) for p in first), "config5 empty page"
            qps5, ms5 = timed_throughput(run_batch5, bs5, n_threads)
            configs["8shard_qtf_top1000"] = {
                "qps": round(qps5, 2),
                "ms_per_batch": round(ms5, 2),
                "shards": n_shards, "from": from5}
            log(f"[bench] config 8shard_qtf_top1000: "
                f"{configs['8shard_qtf_top1000']['qps']} QPS")

            # ---- mesh collective plane, same 8 shards, ONE program -----
            # (parallel/mesh_engine.py): the 8 shard engines folded onto a
            # 1-device ("dp","shard") mesh (spd=8) — per-shard emit, local
            # shard-block merge, all_gather re-top-k and psum counts all
            # IN-PROGRAM, vs the RPC path's per-shard dispatch + host
            # merge above. On a v5e-8 the same program spreads the shard
            # axis over ICI; this measures it on the hardware we have.
            if os.environ.get("BENCH_MESH", "1") == "1":
                from elasticsearch_tpu.parallel import make_mesh
                from elasticsearch_tpu.parallel.mesh_engine import (
                    MeshEngineSearcher)
                from elasticsearch_tpu.search import dfs as dfs_mod
                from elasticsearch_tpu.search.query_dsl import parse_query
                t0 = time.perf_counter()
                mesh1 = make_mesh(dp=1, shard=1, devices=[dev])
                msearch = MeshEngineSearcher(mesh1, engines5, ms_map)
                pack_s = time.perf_counter() - t0
                bodies5 = [{"query": {"match": {"body": tx}}, "size": k5}
                           for tx in texts[:batch * 4]]
                mb = [bodies5[i:i + batch]
                      for i in range(0, len(bodies5), batch)]
                t0 = time.perf_counter()
                out0 = msearch.search_batch(mb[0])
                mesh_compile = time.perf_counter() - t0

                # parity vs the dfs RPC oracle (reader device arrays are
                # already resident in searchers5)
                readers5 = [s.reader for s in searchers5]

                def oracle_one(body):
                    query = parse_query(body["query"])
                    stats = dfs_mod.to_execution_stats(dfs_mod.aggregate_dfs(
                        [dfs_mod.shard_dfs(r, ms_map, query)
                         for r in readers5]))
                    req = parse_search_request(body)
                    rows, total = [], 0
                    for si, r in enumerate(readers5):
                        res = ShardSearcher(
                            si, r, ms_map, dfs_stats=stats).query_phase(req)
                        total += res.total
                        for pos in range(len(res.doc_ids)):
                            seg, local = r.resolve(int(res.doc_ids[pos]))
                            rows.append((float(res.scores[pos]), si,
                                         seg.seg.ids[local]))
                    rows.sort(key=lambda x: (-x[0], x[1]))
                    return total, rows[:k5]

                mesh_ok = True
                for qi in range(int(os.environ.get("BENCH_MESH_PARITY",
                                                   "3"))):
                    total, rows = oracle_one(bodies5[qi])
                    got = [msearch.doc_id(d) for d in out0[qi]["doc_ids"]]
                    want = [did for _, _, did in rows]
                    if out0[qi]["total"] != total:
                        log(f"[bench] mesh parity FAIL q{qi}: "
                            f"total {out0[qi]['total']} vs {total}")
                        mesh_ok = False
                    elif not ids_match_with_tolerance(
                            got, want, f"mesh q{qi}"):
                        mesh_ok = False
                qps_m, ms_m = timed_throughput(
                    msearch.search_batch, mb, n_threads)
                configs["mesh_8shard_top1000"] = {
                    "qps": round(qps_m, 2),
                    "ms_per_batch": round(ms_m, 2),
                    "parity_ok": mesh_ok, "pack_s": round(pack_s, 1),
                    "compile_s": round(mesh_compile, 1), "spd": 8}
                log(f"[bench] config mesh_8shard_top1000: "
                    f"{configs['mesh_8shard_top1000']['qps']} QPS "
                    f"(parity_ok={mesh_ok}, pack {pack_s:.1f}s, "
                    f"compile {mesh_compile:.1f}s)")

                # ---- generalized plane: the SAME config-5 corpus with a
                # numeric sort + terms agg, all in-program (round-5
                # eligibility expansion — sort keys ride the all_gather
                # merge, bucket counts reduce over the shard axis)
                gbodies = [{"query": {"match": {"body": tx}}, "size": k5,
                            "sort": [{"rank": {"order": "desc"}}],
                            "aggs": {"by_cat": {"terms": {
                                "field": "cat", "size": 8}}}}
                           for tx in texts[:batch * 4]]
                t0 = time.perf_counter()
                out_g = msearch.search_batch(gbodies[:batch])
                gen_compile = time.perf_counter() - t0
                # parity q0: totals, rank-descending order, bucket counts
                # vs a brute-force numpy oracle over the packed corpus
                qt = np.array(
                    [term_names.index(w) for w in texts[0].split()
                     if w in term_names], np.int64)
                # uterms may carry kernel-section pad rows past n_docs
                hit = np.isin(uterms[:n_docs], qt).any(axis=1)
                gen_ok = True
                if out_g[0]["total"] != int(hit.sum()):
                    log(f"[bench] generalized-plane parity FAIL: total "
                        f"{out_g[0]['total']} vs {int(hit.sum())}")
                    gen_ok = False
                hit_idx = np.nonzero(hit)[0]
                want_ids = [str(hit_idx[j]) for j in
                            np.argsort(-rank_all[hit_idx],
                                       kind="stable")[:k5]]
                got_ids = [msearch.doc_id(d)
                           for d in out_g[0]["doc_ids"]]
                if not ids_match_with_tolerance(
                        got_ids, want_ids, "generalized-plane sort"):
                    gen_ok = False
                from collections import Counter as _Counter
                cnt = _Counter(int(c) for c in cat_all[hit])
                want_buckets = sorted(
                    ((cat_names[t], n) for t, n in cnt.items()),
                    key=lambda kv: (-kv[1], kv[0]))[:8]
                got_buckets = [
                    (b["key"], b["doc_count"]) for b in
                    out_g[0]["aggregations"]["by_cat"]["buckets"]]
                if got_buckets != want_buckets:
                    log(f"[bench] generalized-plane parity FAIL: "
                        f"buckets {got_buckets} vs {want_buckets}")
                    gen_ok = False
                gmb = [gbodies[i:i + batch]
                       for i in range(0, len(gbodies), batch)]
                qps_g, ms_g = timed_throughput(
                    msearch.search_batch, gmb, n_threads)
                configs["mesh_8shard_sorted_terms_agg"] = {
                    "qps": round(qps_g, 2),
                    "ms_per_batch": round(ms_g, 2),
                    "parity_ok": gen_ok,
                    "compile_s": round(gen_compile, 1), "spd": 8}
                log(f"[bench] config mesh_8shard_sorted_terms_agg "
                    f"(rank sort + terms agg in-program): "
                    f"{configs['mesh_8shard_sorted_terms_agg']['qps']} "
                    f"QPS (parity_ok={gen_ok}, "
                    f"compile {gen_compile:.1f}s)")
            shard_pool.shutdown(wait=False)
            for e5 in engines5:
                e5.close()

        # ---- HBM over-capacity streaming (SURVEY §7 residency) ---------
        # One engine, 8 segments, and a reader budgeted to HALF of them:
        # emulates a corpus at 2x HBM capacity — the overflow half
        # streams host→HBM per batch, double-buffered
        # (jit_exec.run_segments_streamed), vs the fully-resident reader.
        if os.environ.get("BENCH_STREAM", "1") == "1":
            from elasticsearch_tpu.index.device_reader import DeviceReader
            eng_s = Engine(Path(tempfile.mkdtemp(prefix="bench_stream_")),
                           ms_map)
            per_seg = -(-n_docs // 8)
            t0 = time.perf_counter()
            for si in range(8):
                lo = si * per_seg
                hi = min(lo + per_seg, n_docs)
                rows = hi - lo
                np_rows = doc_count_bucket(rows)

                def tpad(a, fill):
                    out = np.full((np_rows,) + a.shape[1:], fill, a.dtype)
                    out[:rows] = a[lo:hi]
                    return out
                seg_df = np.zeros(vocab, np.int64)
                sut = uterms[lo:hi]
                np.add.at(seg_df, sut[sut >= 0], 1)
                eng_s.install_segment(Segment.from_packed_text(
                    si, "body", terms=term_names, tokens=None,
                    uterms=tpad(uterms, -1), utf=tpad(utf, 0.0),
                    doc_len=tpad(lens, 0), df=seg_df, num_docs=rows,
                    ids=[str(lo + i) for i in range(rows)] +
                        [""] * (np_rows - rows)), track_versions=False)
            view_s = eng_s.acquire_searcher()
            half = sum(s.memory_bytes() for s in view_s.segments[:4])
            log(f"[bench] stream: 8-segment engine built in "
                f"{time.perf_counter() - t0:.1f}s; budget {half/1e6:.0f} MB "
                f"(4 of 8 segments resident)")
            reqs_s = [parse_search_request(
                {"query": {"match": {"body": tx}}, "size": k})
                for tx in texts[:batch * 4]]
            bss = [reqs_s[i:i + batch]
                   for i in range(0, len(reqs_s), batch)]

            def measure_reader(reader, label):
                s_ = ShardSearcher(0, reader, ms_map)
                r0 = s_.query_phase_batch(bss[0])
                assert r0 is not None, f"{label} fell back"
                # keep only doc ids: each result holds a `reader` ref and
                # would pin the resident reader's HBM through the
                # streamed measurement
                ids0 = [r.doc_ids for r in r0]
                del r0
                # serial on purpose: the streamed reader's per-batch H2D
                # staging is the thing under test; a pool would interleave
                # two batches' transfers and blur the overlap measurement
                qps, ms = timed_throughput(s_.query_phase_batch, bss)
                return ids0, ms, qps

            import gc as _gc
            r_full = DeviceReader(view_s, device=dev)
            res_f, ms_f, qps_f = measure_reader(r_full, "resident")
            del r_full
            _gc.collect()
            r_half = DeviceReader(view_s, device=dev,
                                  hbm_budget_bytes=half)
            assert sum(s.resident for s in r_half.segments) == 4
            res_h, ms_h, qps_h = measure_reader(r_half, "streamed")
            stream_ok = all(np.array_equal(a, b)
                            for a, b in zip(res_f, res_h))
            ratio = ms_h / ms_f if ms_f else float("inf")
            # attribute the overhead: the streamed half re-crosses
            # host→HBM every batch, so the floor is bytes/bandwidth.
            # Measure THIS host's H2D bandwidth directly.
            probe_mb = 64
            probe = np.zeros((probe_mb << 20) // 4, np.float32)
            jax.device_put(probe, dev).block_until_ready()   # warm
            t0 = time.perf_counter()
            jax.device_put(probe, dev).block_until_ready()
            h2d_mbps = probe_mb / (time.perf_counter() - t0)
            streamed_bytes = sum(
                s.seg.memory_bytes() for s in r_half.segments
                if not s.resident)
            predicted_ms = streamed_bytes / (h2d_mbps * 1e6) * 1e3
            # ---- overlap quantification (round-5): how much of the
            # smaller leg the threaded prefetch pipeline hides. W >= max(Tc, Tt) always;
            # overlap = (Tc + Tt - W) / min(Tc, Tt), 1.0 = fully hidden.
            # Tc = the same segments' compute with everything resident
            # (ms_f); Tt = measured-bandwidth transfer floor. The bw
            # probe is a single 64 MB blocking put, so Tt carries its
            # error — clamp and report the raw legs alongside.
            from elasticsearch_tpu.search import jit_exec as _jx
            st = getattr(_jx.run_segments_streamed, "last_stats", None)
            put_wait_ms = round(st["put_wait_s"] * 1e3, 1) if st else None
            t_c, t_t, w_ = ms_f, predicted_ms, ms_h
            overlap = (t_c + t_t - w_) / min(t_c, t_t) if min(t_c, t_t) \
                else 0.0
            overlap = max(0.0, min(1.0, overlap))
            engine["stream_2x_capacity"] = {
                "resident_qps": round(qps_f, 2),
                "streamed_qps": round(qps_h, 2),
                "ms_per_batch_resident": round(ms_f, 2),
                "ms_per_batch_streamed": round(ms_h, 2),
                "overhead_x": round(ratio, 2), "parity_ok": stream_ok,
                "h2d_mbps": round(h2d_mbps, 1),
                "streamed_mb_per_batch": round(streamed_bytes / 1e6, 1),
                "predicted_transfer_ms": round(predicted_ms, 1),
                "overlap_hidden_frac": round(overlap, 3),
                "put_wait_ms_per_batch": put_wait_ms,
                "compute_leg_ms": round(t_c, 1)}
            log(f"[bench] stream 2x-capacity: resident {qps_f:.1f} QPS "
                f"vs streamed {qps_h:.1f} QPS (overhead {ratio:.2f}x, "
                f"parity_ok={stream_ok}; H2D {h2d_mbps:.0f} MB/s, "
                f"{streamed_bytes/1e6:.0f} MB/batch → predicted "
                f"transfer {predicted_ms:.0f} ms)")
            log(f"[bench] stream overlap: {overlap*100:.0f}% of the "
                f"smaller leg hidden (compute {t_c:.0f} ms inside "
                f"transfer {t_t:.0f} ms; wall {w_:.0f} ms)")
            del r_half
            _gc.collect()
            eng_s.close()

    # ---- percolate leg: persistent registry + one-dispatch matching -------
    # N standing queries × one probe doc: the serial number is the
    # pre-registry per-query loop (percolate_serial, the in-repo oracle);
    # the batched number is the fused registry path; the mpercolate number
    # packs a multi-doc batch into one dispatch per plan shape. Registry
    # program hits/misses ride the record so a cold-cache run is visible.
    perc_record = None
    if os.environ.get("BENCH_PERCOLATE", "1") == "1":
        from elasticsearch_tpu.cluster.state import IndexMetadata
        from elasticsearch_tpu.search import percolator as perc_mod
        from elasticsearch_tpu.search import jit_exec as _jx_p
        perc_record = {}
        pvocab = [f"pw{i:03d}" for i in range(200)]
        prng = np.random.default_rng(77)

        def reg_body(i: int) -> dict:
            w = pvocab[int(prng.integers(0, len(pvocab)))]
            kind = i % 3
            if kind == 0:
                qq = {"match": {"body":
                                f"{w} {pvocab[(i * 7) % len(pvocab)]}"}}
            elif kind == 1:
                qq = {"term": {"cat": w}}
            else:
                qq = {"range": {"rank": {"gte": int(prng.integers(0, 90))}}}
            return {"query": qq, "group": f"g{i % 8}"}

        pdocs = [{"body": " ".join(pvocab[int(j)] for j in
                                   prng.integers(0, len(pvocab), 6)),
                  "cat": pvocab[int(prng.integers(0, len(pvocab)))],
                  "rank": float(prng.integers(0, 100))}
                 for _ in range(12)]
        reg_counts = [int(x) for x in os.environ.get(
            "BENCH_PERCOLATE_REGS", "1000,10000").split(",")]
        for n_regs in reg_counts:
            percs = {f"q{i}": reg_body(i) for i in range(n_regs)}
            pmeta = IndexMetadata(
                name=f"bench_perc_{n_regs}", number_of_shards=1,
                number_of_replicas=0,
                mappings={"_doc": {"properties": {
                    "body": {"type": "text", "analyzer": "whitespace"},
                    "cat": {"type": "keyword"},
                    "rank": {"type": "double"}}}},
                percolators=percs, uuid=f"bench{n_regs}", version=1)
            n_serial = 2 if n_regs <= 1000 else 1
            t0 = time.perf_counter()
            ser0 = None
            for d in pdocs[:n_serial]:
                ser0 = perc_mod.percolate_serial(pmeta, d)
            serial_ms = (time.perf_counter() - t0) / n_serial * 1e3
            b0 = perc_mod.percolate(pmeta, pdocs[0])     # warm (compiles)
            if n_serial == 1:                # ser0 was the same probe doc
                assert b0["total"] == ser0["total"], "percolate parity"
            js0 = _jx_p.cache_stats()
            n_probes = 24
            t0 = time.perf_counter()
            for pi in range(n_probes):
                out_b = perc_mod.percolate(pmeta, pdocs[pi % len(pdocs)])
            batched_ms = (time.perf_counter() - t0) / n_probes * 1e3
            js_mid = _jx_p.cache_stats()
            # parity on the last probe vs the serial oracle
            ser_chk = perc_mod.percolate_serial(
                pmeta, pdocs[(n_probes - 1) % len(pdocs)])
            perc_ok = ([m["_id"] for m in out_b["matches"]] ==
                       [m["_id"] for m in ser_chk["matches"]])
            mitems = [{"doc": d} for d in pdocs]
            perc_mod.percolate_many(pmeta, mitems)       # warm
            t0 = time.perf_counter()
            rounds = 4
            for _ in range(rounds):
                perc_mod.percolate_many(pmeta, mitems)
            mperc_ms = (time.perf_counter() - t0) / (rounds *
                                                     len(mitems)) * 1e3
            js1 = _jx_p.cache_stats()
            reg_st = perc_mod.registry_stats(pmeta.name) or {}
            perc_record[str(n_regs)] = {
                "serial_ms_per_probe": round(serial_ms, 2),
                "batched_ms_per_probe": round(batched_ms, 2),
                "mpercolate_ms_per_probe": round(mperc_ms, 2),
                "speedup_x": round(serial_ms / max(batched_ms, 1e-9), 1),
                "parity_ok": perc_ok,
                # zero once warm: the registry's whole point
                "steady_program_misses":
                    js_mid["percolate_program_misses"]
                    - js0["percolate_program_misses"],
                # first multi-doc pack compiles its stacked shapes once
                "mpercolate_program_misses":
                    js1["percolate_program_misses"]
                    - js_mid["percolate_program_misses"],
                "program_hits": js1["percolate_program_hits"],
                "program_misses": js1["percolate_program_misses"],
                "registry": reg_st,
                "program_costs": program_costs_snapshot(
                    lane_filter=("percolate",)),
            }
            log(f"[bench] percolate {n_regs} regs: serial "
                f"{serial_ms:.1f} ms/probe vs batched {batched_ms:.1f} "
                f"ms/probe ({serial_ms / max(batched_ms, 1e-9):.1f}x), "
                f"mpercolate {mperc_ms:.1f} ms/probe, parity_ok={perc_ok}, "
                f"steady misses "
                f"{perc_record[str(n_regs)]['steady_program_misses']}")

    # ---- refresh_interleave leg: the incremental data plane under churn ---
    # Alternating bulk-index / search at steady state (the north-star
    # continuous-indexing + heavy-search workload): each round appends a
    # doc batch to one shard, refreshes, and immediately searches through
    # a fresh collective-plane pack. `incremental` composes the pack from
    # the per-segment device-block cache (uploads O(new segment));
    # `full_rebuild` is the pre-block-cache baseline (host restack +
    # O(corpus) re-upload per refresh). Program shapes for every slot
    # count are pre-warmed on a throwaway engine set so BOTH modes measure
    # pure data-layer + dispatch cost, not trace/compile. Feeds the
    # eventual real-TPU BENCH_r06 (ROADMAP #1) — on CPU the host→device
    # copy is a memcpy, so the on-chip gap (PCIe/ICI transfer) is wider.
    ri_record = None
    if os.environ.get("BENCH_REFRESH_INTERLEAVE", "1") == "1":
        import tempfile
        from pathlib import Path
        from elasticsearch_tpu.index.engine import Engine
        from elasticsearch_tpu.index.segment import (
            Segment, doc_count_bucket)
        from elasticsearch_tpu.mapping import MapperService
        from elasticsearch_tpu.parallel import make_mesh
        from elasticsearch_tpu.parallel.mesh_engine import (
            MeshEngineSearcher)
        from elasticsearch_tpu.search import jit_exec as _jx_ri

        ri_docs = int(os.environ.get("BENCH_RI_DOCS", 200_000))
        ri_shards = 4
        ri_rounds = int(os.environ.get("BENCH_RI_ROUNDS", 5))
        ri_batch = int(os.environ.get("BENCH_RI_BATCH", 100))
        ri_vocab = 5000
        ri_rng = np.random.default_rng(97)
        ri_terms = [f"r{i:04d}" for i in range(ri_vocab)]
        u_ri, f_ri, l_ri, df_ri, _ = make_corpus(
            ri_rng, ri_docs, ri_vocab, 48, 64)
        ri_map = MapperService()
        ri_map.merge("_doc", {"properties": {
            "body": {"type": "text", "analyzer": "whitespace"}}})
        per_ri = -(-ri_docs // ri_shards)
        ri_mesh = make_mesh(dp=1, shard=1, devices=[dev])
        ri_bodies = [{"query": {"match": {
            "body": " ".join(ri_terms[int(t)] for t in
                             make_queries(ri_rng, 1, ri_vocab, 3,
                                          df_ri)[0])}},
            "size": 10} for _ in range(ri_rounds + 1)]
        # identical churn docs each pass → identical slot layouts →
        # shared program shapes across warm/incremental/full passes
        churn = [[{"body": " ".join(
            ri_terms[int(t)] for t in ri_rng.integers(
                0, ri_vocab, 8))} for _ in range(ri_batch)]
            for _ in range(ri_rounds)]

        def ri_engines():
            engines = []
            for si in range(ri_shards):
                lo, hi = si * per_ri, min((si + 1) * per_ri, ri_docs)
                rows = hi - lo
                np_rows = doc_count_bucket(rows)

                def rpad(a, fill):
                    out = np.full((np_rows,) + a.shape[1:], fill, a.dtype)
                    out[:rows] = a[lo:hi]
                    return out
                seg_df = np.zeros(ri_vocab, np.int64)
                sut = u_ri[lo:hi]
                np.add.at(seg_df, sut[sut >= 0], 1)
                seg = Segment.from_packed_text(
                    0, "body", terms=ri_terms, tokens=None,
                    uterms=rpad(u_ri, -1), utf=rpad(f_ri, 0.0),
                    doc_len=rpad(l_ri, 0), df=seg_df, num_docs=rows,
                    ids=[f"d{lo + i}" for i in range(rows)] +
                        [""] * (np_rows - rows))
                e = Engine(Path(tempfile.mkdtemp(prefix="bench_ri_")),
                           ri_map)
                e.install_segment(seg, track_versions=False)
                engines.append(e)
            return engines

        def ri_pass(reuse: bool, record_rounds: bool):
            engines = ri_engines()
            rounds = []
            bytes_per_refresh = []
            try:
                ms = MeshEngineSearcher(ri_mesh, engines, ri_map,
                                        reuse_blocks=reuse)
                ms.search_batch([ri_bodies[0]])      # warm gen-0 shape
                for r in range(ri_rounds):
                    dl0 = _jx_ri.cache_stats()["data_layer"]
                    t0 = time.perf_counter()
                    for di, doc in enumerate(churn[r]):
                        engines[0].index(f"c{r}-{di}", doc)
                    engines[0].refresh()
                    ms = MeshEngineSearcher(
                        ri_mesh, engines, ri_map, prev=ms,
                        reuse_blocks=reuse)
                    out = ms.search_batch([ri_bodies[r + 1]])
                    assert out[0]["total"] >= 0
                    rounds.append((time.perf_counter() - t0) * 1e3)
                    dl1 = _jx_ri.cache_stats()["data_layer"]
                    bytes_per_refresh.append(
                        dl1["bytes_uploaded"] - dl0["bytes_uploaded"])
            finally:
                for e in engines:
                    e.close()
            if not record_rounds:
                return None
            rs = sorted(rounds)
            tail = lat_pcts(rounds)
            return {"refresh_to_first_search_ms_p50":
                    round(rs[len(rs) // 2], 2),
                    "refresh_to_first_search_ms_p99": tail["p99_ms"],
                    "refresh_to_first_search_ms_p999": tail["p999_ms"],
                    "refresh_to_first_search_ms_mean":
                    round(sum(rounds) / len(rounds), 2),
                    "bytes_uploaded_per_refresh":
                    int(sum(bytes_per_refresh) / len(bytes_per_refresh)),
                    "rounds_ms": [round(x, 2) for x in rounds]}

        t0 = time.perf_counter()
        ri_pass(True, False)            # program shapes for 1..R slots
        warm_s = time.perf_counter() - t0
        inc = ri_pass(True, True)
        full = ri_pass(False, True)
        ri_record = {
            "n_docs": ri_docs, "shards": ri_shards,
            "rounds": ri_rounds, "batch_docs": ri_batch,
            "incremental": inc, "full_rebuild": full,
            "speedup_x": round(
                full["refresh_to_first_search_ms_mean"]
                / max(inc["refresh_to_first_search_ms_mean"], 1e-9), 2),
            "upload_ratio": round(
                full["bytes_uploaded_per_refresh"]
                / max(inc["bytes_uploaded_per_refresh"], 1), 1),
            "warm_compile_s": round(warm_s, 1),
        }
        log(f"[bench] refresh_interleave: incremental "
            f"{inc['refresh_to_first_search_ms_mean']:.1f} ms/refresh "
            f"({inc['bytes_uploaded_per_refresh'] / 1e6:.2f} MB up) vs "
            f"full rebuild "
            f"{full['refresh_to_first_search_ms_mean']:.1f} ms "
            f"({full['bytes_uploaded_per_refresh'] / 1e6:.2f} MB up) — "
            f"{ri_record['speedup_x']}x faster, "
            f"{ri_record['upload_ratio']}x fewer bytes/refresh")

    # ---- impact_pruning leg: quantized eager impacts + block-max sweep ----
    # Exact forward kernel vs impact-eager (precomputed quantized
    # impacts, no per-doc BM25 float math) vs block-max pruned sweep on
    # a skewed top-k workload (rare-leaning query terms — the needle
    # queries WAND-style pruning exists for). Stamps blocks scored /
    # skipped, the effective-work ratio, steady-state program-cache
    # counters, and the parity verdicts. CPU artifacts keep
    # `"fallback": true`; the on-chip capture rides BENCH_r06
    # (ROADMAP #1).
    imp_record = None
    if os.environ.get("BENCH_IMPACT", "1") == "1":
        from elasticsearch_tpu.index.segment import (TextFieldColumn,
                                                     build_impact_column)
        from elasticsearch_tpu.search import jit_exec as _jx_imp
        imp_k = int(os.environ.get("BENCH_IMPACT_K", 10))
        imp_t = int(os.environ.get("BENCH_IMPACT_TERMS", 3))
        imp_batch = int(os.environ.get("BENCH_IMPACT_BATCH",
                                       min(batch, 32)))
        imp_nb = int(os.environ.get("BENCH_IMPACT_BATCHES", 4))
        imp_rows = int(os.environ.get("BENCH_IMPACT_BLOCK_ROWS", 2048))
        # uint16 on the bench: at 16-bit width the quantization bound is
        # far below any top-10 score gap of the skewed workload, so the
        # lane's hits are expected IDENTICAL to the exact scorer (uint8
        # remains the index default — its wider step is what makes the
        # df-drift requant threshold survivable under refresh churn)
        imp_bits = int(os.environ.get("BENCH_IMPACT_BITS", 16))
        # skewed workload: rare-leaning terms (df fraction 2e-5..2e-4)
        lo_df = max(2, int(2e-5 * n_docs))
        hi_df = max(lo_df + 2, int(2e-4 * n_docs))
        cand = np.nonzero((df >= lo_df) & (df <= hi_df))[0]
        if cand.size < imp_t:
            cand = np.nonzero(df > 0)[0]
        q_imp = rng.choice(cand, size=(imp_nb * imp_batch,
                                       imp_t)).astype(np.int32)
        t0 = time.perf_counter()
        imp_col = TextFieldColumn(
            terms=[str(i) for i in range(vocab)],
            tokens=np.zeros((1, 1), np.int32),
            uterms=uterms, utf=utf, doc_len=lens_p,
            df=df.astype(np.int64), total_tokens=int(lens.sum()))
        icol = build_impact_column(
            imp_col, df=df, doc_count=n_docs, avgdl=avgdl,
            k1=p.k1, b=p.b, bits=imp_bits, block_rows=imp_rows,
            block_budget=1 << 28)
        imp_build_s = time.perf_counter() - t0
        log(f"[bench] impact columns built in {imp_build_s:.1f}s "
            f"(scale={icol.scale:.5f}, "
            f"blocks={icol.qimp.shape[0] // icol.block_rows}, "
            f"block_max={0 if icol.block_max is None else icol.block_max.nbytes} B)")
        imp_cfg = _jx_imp.ImpactPlaneConfig(bits=imp_bits,
                                            block_rows=imp_rows)
        pack = _jx_imp._ImpactPack("t", imp_cfg, p.k1, p.b)
        # the engine section released the kernel arrays' HBM — the leg
        # carries its own uploads
        di_ut = jax.device_put(jnp.asarray(uterms), dev)
        di_utf = jax.device_put(jnp.asarray(utf), dev)
        di_len = jax.device_put(jnp.asarray(lens_p), dev)
        di_live = jax.device_put(jnp.asarray(live_np), dev)
        d_qimp = jax.device_put(jnp.asarray(icol.qimp), dev)
        d_bmax = jax.device_put(jnp.asarray(icol.block_max), dev)
        n_blocks = icol.qimp.shape[0] // icol.block_rows
        pack.segs.append({
            "uterms": di_ut, "live": di_live, "qimp": d_qimp,
            "block_max": d_bmax, "scale": float(icol.scale),
            "host": imp_col, "np_docs": n_pad, "u": uterms.shape[1],
            "doc_base": 0, "n_blocks": n_blocks})
        pack.bases.append(0)
        pack.total_blocks = n_blocks
        pack.bound_per_term = icol.bound_per_term
        pack.scales = jnp.asarray([icol.scale], jnp.float32)
        term_rows = [[str(int(t)) for t in row] for row in q_imp]
        ones = [1.0] * imp_batch
        nocur = [None] * imp_batch

        def imp_exact(bi):
            qt = q_imp[bi * imp_batch:(bi + 1) * imp_batch]
            s, d_ = bm25_topk_batch(
                di_ut, di_utf, di_len, di_live,
                jax.device_put(jnp.asarray(qt), dev),
                jax.device_put(jnp.asarray(idf_table[qt]), dev),
                np.float32(avgdl), imp_k, p.k1, p.b)
            return np.asarray(s), np.asarray(d_)

        def imp_eager(bi):
            out = _jx_imp.run_impact_batch(
                pack, term_rows[bi * imp_batch:(bi + 1) * imp_batch],
                ones, nocur, k=imp_k)
            return np.asarray(out["top_scores"]), \
                np.asarray(out["top_docs"])

        def imp_pruned(bi):
            out = _jx_imp.run_impact_pruned(
                pack, term_rows[bi * imp_batch:(bi + 1) * imp_batch],
                ones, nocur, k=imp_k)
            return {name: np.asarray(v) for name, v in out.items()}

        def imp_ms(run):
            t0 = time.perf_counter()
            for bi in range(imp_nb):
                run(bi)
            return (time.perf_counter() - t0) * 1e3 / imp_nb

        imp_exact(0)                     # warm: one compile per lane,
        imp_eager(0)                     # OUTSIDE the steady-state
        imp_pruned(0)                    # compile-counter window
        js0 = _jx_imp.cache_stats()
        exact_ms = imp_ms(imp_exact)
        eager_ms = imp_ms(imp_eager)
        pruned_ms = imp_ms(imp_pruned)
        js1 = _jx_imp.cache_stats()
        steady_compiles = js1["misses"] - js0["misses"]
        # parity: eager vs exact (rank/id with quantization-tie
        # tolerance; scores within the documented bound), pruned vs
        # eager EXACT (ids + bit-equal scores)
        es, ed = imp_exact(0)
        gs, gd = imp_eager(0)
        pr = imp_pruned(0)
        imp_parity = True
        imp_rank_identical = True
        tol = pack.bound_per_term * imp_t + 1e-4
        for qi in range(imp_batch):
            imp_rank_identical &= (
                list(gd[qi]) == list(ed[qi]))
            # exact-scorer reference for THIS query: every returned doc
            # must score within the quantization bound of its exact
            # score AND be a true top-k member up to bound-sized ties
            qrow = q_imp[qi]
            ref = np.zeros(n_docs, np.float32)
            for t_ in qrow:
                col_ = mat.getcol(int(t_))
                ref[col_.indices] += idf_table[int(t_)] * col_.data
            kth = float(np.partition(ref, -imp_k)[-imp_k]) \
                if n_docs > imp_k else float(ref.min())
            for d_, s_ in zip(gd[qi], gs[qi]):
                if d_ < 0:
                    continue
                if d_ >= n_docs or abs(float(s_) - ref[d_]) > tol:
                    log(f"[bench] impact q{qi}: doc {d_} score "
                        f"{s_:.4f} vs exact {ref[min(d_, n_docs-1)]:.4f}"
                        f" off by > bound {tol:.4f}")
                    imp_parity = False
                elif ref[d_] < kth - tol:
                    log(f"[bench] impact q{qi}: doc {d_} is not a "
                        f"top-{imp_k} member (exact {ref[d_]:.4f} < "
                        f"kth {kth:.4f} - bound)")
                    imp_parity = False
        if not imp_rank_identical:
            log("[bench] impact-eager rank order differs from exact "
                "somewhere (quantization ties) — member/score parity "
                f"{'held' if imp_parity else 'FAILED'}")
        pruned_identical = bool(
            np.array_equal(pr["top_docs"], gd)
            and np.array_equal(pr["top_scores"], gs))
        scored = skipped = 0
        for bi in range(imp_nb):
            out = imp_pruned(bi)
            scored += int(out["blocks_scored"].sum())
            skipped += int(out["blocks_skipped"].sum())
        total_blk = scored + skipped
        # expected-work model (ROOFLINE "block-max" section): a block
        # with NO query term has bound 0 and always skips, so the
        # occupied-block union is the model's ceiling on effective work;
        # theta-pruning trims the low-bound tail below it
        p_t = 1.0 - (1.0 - df[q_imp].astype(np.float64)
                     / max(n_docs, 1)) ** imp_rows
        pred_occ = float(np.mean(1.0 - np.prod(1.0 - p_t, axis=1)))
        imp_record = {
            "n_docs": n_docs, "k": imp_k, "terms": imp_t,
            "batch": imp_batch, "block_rows": imp_rows,
            "blocks_total": n_blocks,
            "impact_build_s": round(imp_build_s, 2),
            "impact_bytes": int(icol.qimp.nbytes),
            "block_max_bytes": 0 if icol.block_max is None
            else int(icol.block_max.nbytes),
            "exact_ms_per_batch": round(exact_ms, 2),
            "impact_eager_ms_per_batch": round(eager_ms, 2),
            "blockmax_ms_per_batch": round(pruned_ms, 2),
            "eager_vs_exact_speedup": round(exact_ms
                                            / max(eager_ms, 1e-9), 3),
            "blocks_scored": scored,
            "blocks_skipped": skipped,
            "skip_ratio": round(skipped / max(total_blk, 1), 4),
            "effective_work_ratio": round(scored / max(total_blk, 1),
                                          4),
            "predicted_occupied_frac": round(pred_occ, 4),
            "steady_state_compiles": steady_compiles,
            "bits": imp_bits,
            "parity_eager_vs_exact": imp_parity,
            "rank_identical_to_exact": imp_rank_identical,
            "pruned_identical_to_eager": pruned_identical,
            "bound_per_term": round(float(pack.bound_per_term), 6),
            "program_costs": program_costs_snapshot(
                lane_filter=("impact-eager", "impact-pruned")),
        }
        log(f"[bench] impact_pruning: exact {exact_ms:.1f} ms/batch, "
            f"eager {eager_ms:.1f} ms/batch "
            f"({imp_record['eager_vs_exact_speedup']}x), blockmax "
            f"{pruned_ms:.1f} ms/batch, skip_ratio "
            f"{imp_record['skip_ratio']} "
            f"({skipped}/{total_blk} blocks), parity "
            f"eager={imp_parity} pruned_identical={pruned_identical}")

    # ---- fault_recovery leg: degraded-mode serving under device faults ----
    # Steady-state QPS on the collective plane, QPS during an injected
    # device-fault burst (breaker open, fan-out/eager serving — requests
    # keep succeeding), and time-to-plane-reopen after the faults heal
    # (half-open probe within the backoff bound). CPU now; the on-chip
    # number rides the eventual real-TPU BENCH_r06 (ROADMAP #1).
    fr_record = None
    if os.environ.get("BENCH_FAULT_RECOVERY", "1") == "1":
        import tempfile
        from pathlib import Path as _FRPath
        from elasticsearch_tpu.node import Node as _FRNode
        from elasticsearch_tpu.search import jit_exec as _jx_fr
        from elasticsearch_tpu.testing_disruption import DeviceFaultScheme

        fr_docs = int(os.environ.get("BENCH_FR_DOCS", 5000))
        fr_queries = int(os.environ.get("BENCH_FR_QUERIES", 120))
        fr_rng = np.random.default_rng(99)
        fr_node = _FRNode({}, data_path=_FRPath(
            tempfile.mkdtemp(prefix="bench_fr_")) / "n").start()
        try:
            fr_node.indices_service.create_index("fr", {
                "settings": {"number_of_shards": 4,
                             "number_of_replicas": 0},
                "mappings": {"_doc": {"properties": {
                    "t": {"type": "text", "analyzer": "whitespace"},
                    "v": {"type": "long"}}}}})
            for i in range(fr_docs):
                words = " ".join(f"w{int(x)}" for x in
                                 fr_rng.zipf(1.5, 6) if x < 60)
                fr_node.index_doc("fr", str(i),
                                  {"t": words or "w1", "v": i})
            fr_node.broadcast_actions.refresh("fr")
            fr_body = {"query": {"match": {"t": "w1 w3"}}, "size": 10}
            _jx_fr.plane_breaker.reset()
            _jx_fr.plane_breaker.configure(threshold=3, backoff_s=0.25,
                                           max_backoff_s=5.0)
            fr_node.search("fr", dict(fr_body))      # warm (compiles)
            time.sleep(0.3)                          # drain plane warm

            def fr_qps(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    out = fr_node.search("fr", dict(fr_body))
                    assert out["hits"]["total"] >= 0
                return n / (time.perf_counter() - t0)

            steady_qps = fr_qps(fr_queries)
            scheme = DeviceFaultScheme(seed=42, p=1.0,
                                       reset_breaker_on_stop=False)
            scheme.start_disrupting()
            try:
                t_burst = time.perf_counter()
                open_after = None
                burst_t0 = time.perf_counter()
                for qi in range(fr_queries):
                    fr_node.search("fr", dict(fr_body))
                    if open_after is None and \
                            _jx_fr.plane_breaker.stats()["state"] \
                            == "open":
                        open_after = qi + 1
                        t_open_ms = (time.perf_counter()
                                     - t_burst) * 1e3
                burst_qps = fr_queries / (time.perf_counter() - burst_t0)
                st_open = _jx_fr.plane_breaker.stats()
                scheme.heal()                    # faults gone, hook counts
                t_heal = time.perf_counter()
                reopen_ms = None
                deadline = time.perf_counter() + 10.0
                while time.perf_counter() < deadline:
                    fr_node.search("fr", dict(fr_body))
                    if _jx_fr.plane_breaker.stats()["state"] == "closed":
                        reopen_ms = (time.perf_counter() - t_heal) * 1e3
                        break
                    time.sleep(0.02)
            finally:
                scheme.stop_disrupting()
                _jx_fr.plane_breaker.reset()
            fr_record = {
                "n_docs": fr_docs, "queries": fr_queries,
                "steady_qps": round(steady_qps, 1),
                "fault_burst_qps": round(burst_qps, 1),
                "degraded_qps_ratio": round(burst_qps
                                            / max(steady_qps, 1e-9), 3),
                "breaker_opened": st_open["state"] == "open",
                "errors_to_open": open_after,
                "time_to_open_ms": round(t_open_ms, 2)
                if open_after is not None else None,
                "time_to_plane_reopen_ms": round(reopen_ms, 2)
                if reopen_ms is not None else None,
                "injected_faults": scheme.total_injected,
                "breaker": st_open,
            }
            log(f"[bench] fault_recovery: steady {steady_qps:.1f} QPS, "
                f"burst {burst_qps:.1f} QPS (breaker "
                f"{'opened after ' + str(open_after) + ' requests' if open_after else 'never opened'}), "
                f"plane reopened in "
                f"{fr_record['time_to_plane_reopen_ms']} ms after heal")
        finally:
            fr_node.close()

    # ---- tail_tolerance leg: hedged scatter-gather under a brownout -------
    # One replica copy browns out (sustained service delay, no drops).
    # tail_off (ARS + hedging disabled — the pre-PR next-copy-on-error
    # model) pays the brownout delay on every search that touches the
    # slow copy: p99 degrades to the delay. tail_on (defaults) hedges
    # the first slow request at the shard group's latency-histogram
    # quantile and then ARS re-ranks the browned copy last, so p99
    # stays near healthy. Stamps p50/p99/p999 per phase plus the
    # hedges_* counters, reconciled.
    tt_record = None
    if os.environ.get("BENCH_TAIL", "1") == "1":
        from elasticsearch_tpu.testing import InternalTestCluster
        from elasticsearch_tpu.testing_disruption import BrownoutScheme

        tt_docs = int(os.environ.get("BENCH_TT_DOCS", 600))
        tt_queries = int(os.environ.get("BENCH_TT_QUERIES", 150))
        tt_delay_ms = float(os.environ.get("BENCH_TT_DELAY_MS", 150.0))
        tt_body = {"query": {"match": {"body": "shared"}}, "size": 5}

        def tt_lat(coord, n) -> "np.ndarray":
            lat = []
            for _ in range(n):
                t0 = time.perf_counter()
                out = coord.search("tail_bench", dict(tt_body))
                assert out["_shards"]["failed"] == 0, out["_shards"]
                lat.append((time.perf_counter() - t0) * 1e3)
            return np.array(lat)

        def tt_phase(tail_on: bool) -> dict:
            settings = {} if tail_on else {
                "search.ars.enabled": "false",
                "search.hedge.enabled": "false"}
            c = InternalTestCluster(num_nodes=2, settings=settings)
            try:
                a = c.nodes[0]
                a.indices_service.create_index("tail_bench", {"settings": {
                    "number_of_shards": 2, "number_of_replicas": 1,
                    # the leg measures the RPC scatter-gather — the
                    # copy-selection path — not the all-local plane
                    "index.search.collective_plane": "false"}})
                a.wait_for_health("green", timeout=30)
                for i in range(tt_docs):
                    a.index_doc("tail_bench", str(i),
                                {"n": i, "body": f"tok{i % 7} shared"})
                a.broadcast_actions.refresh("tail_bench")
                # coordinator == browned node: its LOCAL copies are the
                # baseline try-order, so the tail layer must actively
                # dodge them (tail_off pays the delay every time)
                coord = c.nodes[0]
                healthy = tt_lat(coord, tt_queries)
                if tail_on:
                    # deterministic hedge demonstration: between two
                    # HEALTHY copies the post-warm-up order is a coin
                    # flip, so re-seed the ARS table with the browned
                    # local copy ranked first — the first browned
                    # search then MUST hedge, and ARS re-ranks from
                    # the hedge's latency-floor observation
                    from elasticsearch_tpu.action.replica_stats import \
                        ReplicaStatsTable
                    rs = ReplicaStatsTable()
                    coord.search_actions.replica_stats = rs
                    rs.observe(coord.node_id, 3.0, service_ms=2.0,
                               queue=0)
                    rs.observe(c.nodes[1].node_id, 4.0, service_ms=3.0,
                               queue=0)
                    for sid in range(2):
                        for _ in range(10):
                            rs.observe_group(("tail_bench", sid), 4.0)
                scheme = BrownoutScheme([coord],
                                        delay_s=tt_delay_ms / 1e3)
                scheme.start_disrupting()
                try:
                    browned = tt_lat(
                        coord, tt_queries if tail_on
                        else max(tt_queries // 4, 20))
                finally:
                    scheme.stop_disrupting()
                hs = coord.search_actions.replica_stats.hedge_stats()
                return {"healthy": lat_pcts(healthy),
                        "browned": lat_pcts(browned), "hedging": hs}
            finally:
                c.close(check_leaks=False)

        off = tt_phase(False)
        on = tt_phase(True)
        hs = on["hedging"]
        tt_record = {
            "n_docs": tt_docs, "queries": tt_queries,
            "brownout_delay_ms": tt_delay_ms,
            "tail_off": off, "tail_on": on,
            # the acceptance pair: unhedged p99 degrades to the
            # brownout delay; hedged p99 stays within 3x healthy
            "unhedged_p99_degraded_to_delay":
                off["browned"]["p99_ms"] >= 0.8 * tt_delay_ms,
            "hedged_p99_within_3x_healthy":
                on["browned"]["p99_ms"]
                <= 3.0 * max(on["healthy"]["p99_ms"], 1.0),
            "counters_reconciled":
                hs["hedges_in_flight"] == 0
                and hs["hedges_launched"]
                == hs["hedges_won"] + hs["hedges_cancelled"],
        }
        log(f"[bench] tail_tolerance: healthy p99 "
            f"{on['healthy']['p99_ms']} ms; browned p99 unhedged "
            f"{off['browned']['p99_ms']} ms vs hedged "
            f"{on['browned']['p99_ms']} ms "
            f"(delay {tt_delay_ms} ms, hedges {hs}); "
            f"within_3x={tt_record['hedged_p99_within_3x_healthy']}, "
            f"degraded={tt_record['unhedged_p99_degraded_to_delay']}, "
            f"reconciled={tt_record['counters_reconciled']}")

    # ---- planner_fusion leg: composed rescore dispatch vs per-lane serial --
    # The cost-driven planner composes impact candidate generation and
    # the window rescore into ONE device dispatch per admitted batch;
    # the pre-planner serving of the same requests is the general
    # per-segment path plus a host re-rank pass per request. Stamps
    # dispatches-per-request on both paths, the fused-vs-sequential RTT
    # ratio, the predicted-vs-measured plan cost error from a profiled
    # response, and the planner admission counters (reconciled against
    # the request count).
    pf_record = None
    if os.environ.get("BENCH_PLANNER", "1") == "1":
        import tempfile as _pf_tmp
        from pathlib import Path as _PfPath

        from elasticsearch_tpu.index.device_reader import \
            device_reader_for as _pf_reader
        from elasticsearch_tpu.node import Node as _PfNode
        from elasticsearch_tpu.observability import costs as _pf_costs
        from elasticsearch_tpu.search import jit_exec as _jx_pf
        from elasticsearch_tpu.search.phase import (
            ShardSearcher as _PfSearcher,
            parse_search_request as _pf_parse)

        pf_docs = int(os.environ.get("BENCH_PLANNER_DOCS", 4000))
        pf_batch = int(os.environ.get("BENCH_PLANNER_BATCH", 16))
        pf_rounds = int(os.environ.get("BENCH_PLANNER_ROUNDS", 6))
        pf_vocab = 120
        pf_rng = np.random.default_rng(31337)
        node_pf = _PfNode({}, data_path=_PfPath(
            _pf_tmp.mkdtemp(prefix="bench_planner_")) / "n").start()
        try:
            node_pf.indices_service.create_index("planner_bench", {
                "settings": {"number_of_shards": 1,
                             "number_of_replicas": 0,
                             "index.search.collective_plane": False,
                             "index.search.impact_plane": True,
                             "index.search.impact.block_rows": 64},
                "mappings": {"_doc": {"properties": {
                    "t": {"type": "text",
                          "analyzer": "whitespace"}}}}})
            for di in range(pf_docs):
                nw = int(pf_rng.integers(4, 13))
                node_pf.index_doc("planner_bench", str(di), {
                    "t": " ".join(
                        f"w{int(w)}" for w in
                        pf_rng.integers(0, pf_vocab, size=nw))})
            node_pf.broadcast_actions.refresh("planner_bench")
            svc_pf = node_pf.indices_service.indices["planner_bench"]
            reader_pf = _pf_reader(svc_pf.engine(0))
            s_fused = _PfSearcher(0, reader_pf, svc_pf.mapper_service,
                                  index_name="planner_bench")
            # the sequential comparator: SAME reader, the composed arm
            # disabled — every rescore request then declines batching
            # (the quantized/exact arms screen out rescore) and serves
            # on the general per-segment path + host re-rank, the
            # pre-planner ladder
            s_seq = _PfSearcher(0, reader_pf, svc_pf.mapper_service,
                                index_name="planner_bench")
            s_seq._rescore_batch_launch = \
                lambda reqs, n_real=None: None
            pf_nreq = pf_batch * pf_rounds
            pf_bodies = []
            for qi in range(pf_nreq):
                t1, t2, t3, t4 = (int(w) for w in
                                  pf_rng.integers(0, pf_vocab, 4))
                pf_bodies.append({
                    "query": {"match": {"t": f"w{t1} w{t2}"}},
                    "size": 10,
                    "rescore": {"window_size": 24, "query": {
                        "rescore_query": {
                            "match": {"t": f"w{t3} w{t4}"}},
                        "query_weight": 1.0,
                        "rescore_query_weight": 1.5,
                        "score_mode": "total"}}})
            pf_reqs = [_pf_parse(b) for b in pf_bodies]
            pf_batches = [pf_reqs[i:i + pf_batch]
                          for i in range(0, pf_nreq, pf_batch)]

            def _pf_disp() -> int:
                return sum(r["dispatches"] for r in
                           _pf_costs.lane_rollup().values())

            t0 = time.perf_counter()
            warm = s_fused.query_phase_batch(pf_batches[0])
            pf_compile_s = time.perf_counter() - t0
            assert warm is not None, "planner_fusion batch fell back"
            d0, st0 = _pf_disp(), _jx_pf.cache_stats()
            t0 = time.perf_counter()
            fused_outs = []
            for pb in pf_batches:
                outs = s_fused.query_phase_batch(pb)
                assert outs is not None, "planner_fusion batch declined"
                fused_outs.extend(outs)
            fused_s = time.perf_counter() - t0
            d1, st1 = _pf_disp(), _jx_pf.cache_stats()
            pf_plans = st1["planner_plans"] - st0["planner_plans"]
            pf_fused = st1["rescore_fused_dispatches"] - \
                st0["rescore_fused_dispatches"]
            # sequential leg: warm the general path's programs first,
            # then time a bounded sample request-at-a-time
            s_seq.query_phase(pf_reqs[0])
            pf_nseq = min(pf_nreq, max(pf_batch * 2, 16))
            d2 = _pf_disp()
            t0 = time.perf_counter()
            seq_outs = [s_seq.query_phase(r) for r in
                        pf_reqs[:pf_nseq]]
            seq_s = time.perf_counter() - t0
            d3 = _pf_disp()
            fused_ms = fused_s * 1e3 / pf_nreq
            seq_ms = seq_s * 1e3 / pf_nseq
            # quantized-vs-exact member overlap (score domains differ
            # by design — the impact index opted into quantization)
            overlap = total_top = 0
            for fo, so in zip(fused_outs[:pf_nseq], seq_outs):
                f_ids = set(np.asarray(fo.doc_ids).tolist())
                overlap += len(f_ids &
                               set(np.asarray(so.doc_ids).tolist()))
                total_top += len(f_ids)
            # predicted-vs-measured: the drain stamps cost_error on the
            # plan.cost span once the lane has a WARM measured price
            # UNDER THIS NODE'S id (cost attribution is per node; the
            # direct-searcher rounds above ran outside a node context),
            # so warm the node-scoped price first, then read the stamp
            # off one profiled response
            for b_pf in pf_bodies[:3]:
                node_pf.search_actions.search("planner_bench", b_pf)
            prof = node_pf.search_actions.search(
                "planner_bench", {**pf_bodies[0], "profile": True})
            pf_cost_error = None
            stack = [t for e in prof["profile"]["shards"]
                     for t in e["spans"]]
            while stack:
                t = stack.pop()
                if t["name"] == "plan.cost" and \
                        "cost_error" in t.get("attrs", {}):
                    pf_cost_error = float(t["attrs"]["cost_error"])
                stack.extend(t.get("children", ()))
            pf_record = {
                "n_docs": pf_docs, "batch": pf_batch,
                "requests_fused": pf_nreq,
                "requests_sequential": pf_nseq,
                "compile_s": round(pf_compile_s, 1),
                "fused_ms_per_request": round(fused_ms, 3),
                "sequential_ms_per_request": round(seq_ms, 3),
                "fused_vs_sequential_rtt_ratio": round(
                    seq_ms / max(fused_ms, 1e-9), 3),
                "dispatches_per_request_fused": round(
                    (d1 - d0) / max(pf_nreq, 1), 4),
                "dispatches_per_request_sequential": round(
                    (d3 - d2) / max(pf_nseq, 1), 4),
                "planner_plans": pf_plans,
                "rescore_fused_dispatches": pf_fused,
                "counters_reconciled": bool(
                    pf_plans == len(pf_batches)
                    and pf_fused == pf_nreq),
                "fused_vs_sequential_recall_at_10": round(
                    overlap / max(total_top, 1), 4),
                "predicted_vs_measured_cost_error": pf_cost_error,
                "planner_fallback_reasons":
                    dict(st1.get("planner_fallback_reasons", {})),
                "program_costs": program_costs_snapshot(
                    lane_filter=("impact-rescore",)),
            }
            log(f"[bench] planner_fusion: fused {fused_ms:.2f} "
                f"ms/req ({pf_record['dispatches_per_request_fused']} "
                f"dispatches/req) vs sequential {seq_ms:.2f} ms/req "
                f"({pf_record['dispatches_per_request_sequential']}"
                f" dispatches/req) — "
                f"{pf_record['fused_vs_sequential_rtt_ratio']}x, "
                f"cost_error={pf_cost_error}, reconciled="
                f"{pf_record['counters_reconciled']}")
        finally:
            node_pf.close()

    # ---- multichip_lanes leg: pod-slice mesh-served impact lane --------
    # Per-geometry QPS of the mesh-sharded block-max lane (ONE compiled
    # shard_map dispatch per geometry: doc-axis sharded columns, θ
    # exchanged cross-chip, all_gather + re-top-k merge), the θ-exchange
    # round count each pruned sweep pays, and the pod-slice scaling
    # ratio vs the single-chip lane — the MULTICHIP_r06 capture's
    # companion numbers. Calls the lane entry points directly (not the
    # searcher) so the planner's measured-cost routing can't bounce the
    # sweep back to the single-chip arm mid-measurement.
    mc_record = None
    if os.environ.get("BENCH_MULTICHIP_LANES", "1") == "1":
        mc_ndev = jax.device_count()
        if mc_ndev < 2:
            mc_record = {"skipped":
                         f"{mc_ndev} device(s); mesh lanes need >= 2"}
            log(f"[bench] multichip_lanes: skipped ({mc_ndev} device)")
        else:
            import tempfile as _mc_tmp
            from pathlib import Path as _McPath

            from elasticsearch_tpu.index.device_reader import \
                device_reader_for as _mc_reader_for
            from elasticsearch_tpu.node import Node as _McNode
            from elasticsearch_tpu.ops import blockmax as _mc_bm
            from elasticsearch_tpu.parallel.mesh import (
                make_mesh as _mc_make_mesh,
                valid_geometries as _mc_geoms)
            from elasticsearch_tpu.search import jit_exec as _jx_mc

            mc_docs = int(os.environ.get("BENCH_MULTICHIP_DOCS", 6000))
            mc_batch = int(os.environ.get("BENCH_MULTICHIP_BATCH", 16))
            mc_nb = int(os.environ.get("BENCH_MULTICHIP_BATCHES", 4))
            mc_k, mc_t, mc_vocab = 10, 3, 120
            mc_rng = np.random.default_rng(60613)
            node_mc = _McNode({}, data_path=_McPath(
                _mc_tmp.mkdtemp(prefix="bench_multichip_")) / "n"
            ).start()
            try:
                node_mc.indices_service.create_index("mc_bench", {
                    "settings": {"number_of_shards": 1,
                                 "number_of_replicas": 0,
                                 "index.search.collective_plane": False,
                                 "index.search.impact_plane": True,
                                 "index.search.impact.block_rows": 64},
                    "mappings": {"_doc": {"properties": {
                        "t": {"type": "text",
                              "analyzer": "whitespace"}}}}})
                for di in range(mc_docs):
                    nw = int(mc_rng.integers(4, 13))
                    node_mc.index_doc("mc_bench", str(di), {
                        "t": " ".join(
                            f"w{int(w)}" for w in
                            mc_rng.integers(0, mc_vocab, size=nw))})
                node_mc.broadcast_actions.refresh("mc_bench")
                svc_mc = node_mc.indices_service.indices["mc_bench"]
                reader_mc = _mc_reader_for(svc_mc.engine(0))
                mc_cfg = _jx_mc.ImpactPlaneConfig(block_rows=64)
                pack_mc = _jx_mc.impact_pack_for(reader_mc, "t", mc_cfg)
                assert pack_mc is not None and pack_mc.can_prune, \
                    "multichip_lanes: no prunable impact columns"
                mc_rows = [[f"w{int(w)}" for w in
                            mc_rng.integers(0, mc_vocab, size=mc_t)]
                           for _ in range(mc_batch)]
                mc_ones = [1.0] * mc_batch
                mc_nocur = [None] * mc_batch

                def mc_single():
                    return _jx_mc.run_impact_pruned(
                        pack_mc, mc_rows, mc_ones, mc_nocur, k=mc_k)

                def mc_ms(run):
                    t0 = time.perf_counter()
                    for _ in range(mc_nb):
                        run()
                    return (time.perf_counter() - t0) * 1e3 / mc_nb

                ref = mc_single()            # warm OUTSIDE the window
                ref_d = np.asarray(ref["top_docs"])
                ref_s = np.asarray(ref["top_scores"])
                single_ms = mc_ms(mc_single)
                mc_geo_recs = {}
                mc_parity = True
                best_qps = 0.0
                for mc_dp, mc_sh in _mc_geoms(mc_ndev):
                    mesh_g = _mc_make_mesh(dp=mc_dp, shard=mc_sh)

                    def mc_mesh(mesh_g=mesh_g):
                        return _jx_mc.run_impact_mesh(
                            reader_mc, pack_mc, mesh_g, mc_rows,
                            mc_ones, mc_nocur, k=mc_k, prune=True)
                    dl0 = _jx_mc.cache_stats()["data_layer"]
                    t0 = time.perf_counter()
                    got = mc_mesh()          # warm: compile + placement
                    g_compile_s = time.perf_counter() - t0
                    dl1 = _jx_mc.cache_stats()["data_layer"]
                    g_ok = bool(
                        np.array_equal(np.asarray(got["top_docs"]),
                                       ref_d)
                        and np.array_equal(
                            np.asarray(got["top_scores"]), ref_s))
                    mc_parity &= g_ok
                    g_ms = mc_ms(mc_mesh)
                    g_qps = mc_batch * 1e3 / max(g_ms, 1e-9)
                    best_qps = max(best_qps, g_qps)
                    mc_geo_recs[f"dp{mc_dp}x{mc_sh}"] = {
                        "dp": mc_dp, "shard": mc_sh,
                        "ms_per_batch": round(g_ms, 2),
                        "qps": round(g_qps, 1),
                        "vs_single_chip": round(
                            single_ms / max(g_ms, 1e-9), 3),
                        "compile_s": round(g_compile_s, 1),
                        "placement_bytes_uploaded":
                            dl1["placement_bytes_uploaded"]
                            - dl0["placement_bytes_uploaded"],
                        "placement_bytes_reused":
                            dl1["placement_bytes_reused"]
                            - dl0["placement_bytes_reused"],
                        "identical_to_single_chip": g_ok,
                    }
                single_qps = mc_batch * 1e3 / max(single_ms, 1e-9)
                mc_record = {
                    "n_docs": mc_docs, "k": mc_k, "terms": mc_t,
                    "batch": mc_batch, "n_devices": mc_ndev,
                    "single_chip_ms_per_batch": round(single_ms, 2),
                    "single_chip_qps": round(single_qps, 1),
                    "geometries": mc_geo_recs,
                    "theta_exchange_rounds":
                        _mc_bm.THETA_EXCHANGE_ROUNDS,
                    "scaling_ratio": round(
                        best_qps / max(single_qps, 1e-9), 3),
                    "parity_all_geometries": mc_parity,
                    "program_costs": program_costs_snapshot(
                        lane_filter=("impact-mesh", "knn-mesh")),
                }
                log(f"[bench] multichip_lanes: single-chip "
                    f"{single_ms:.1f} ms/batch; "
                    + ", ".join(
                        f"{gk} {gv['ms_per_batch']}ms "
                        f"({gv['vs_single_chip']}x)"
                        for gk, gv in mc_geo_recs.items())
                    + f"; θ rounds={mc_record['theta_exchange_rounds']}"
                    f", scaling {mc_record['scaling_ratio']}x, parity "
                    f"{mc_parity}")
            finally:
                node_mc.close()

    oracle_recall = engine.get("oracle_recall_at_k")
    recall_ok = bool(kernel_ok and engine_ok and
                     (oracle_recall is None or oracle_recall >= 0.999))
    qps = engine.get("qps", kernel_qps)
    # collective-plane accounting for the artifact: how often the run's
    # searches stayed on a compiled path (admission rate) and how many
    # shard_map trace+compiles the shape-keyed program cache actually
    # paid (mesh_program_misses) vs re-dispatched (hits)
    from elasticsearch_tpu.search import jit_exec as _jx_stats
    _js = _jx_stats.cache_stats()
    _m_total = _js["mesh_program_hits"] + _js["mesh_program_misses"]
    engine["collective_plane"] = {
        "mesh_dispatches": _m_total,
        "program_compiles": _js["mesh_program_misses"],
        "program_cache_hits": _js["mesh_program_hits"],
        "admission_rate": round(
            _m_total / max(_m_total + _js["plane_fallbacks"], 1), 3),
        "fallback_reasons": _js["fallback_reasons"],
        "program_costs": program_costs_snapshot(lane_filter=("mesh",)),
    }
    log(f"[bench] collective plane: {_m_total} mesh dispatches, "
        f"{_js['mesh_program_misses']} program compiles, "
        f"admission rate "
        f"{engine['collective_plane']['admission_rate']}")
    record = {
        "metric": "bm25_top1000_qps_per_chip",
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": round(qps / cpu_qps, 3),
        # unmistakable not-a-headline marker: a BENCH_PLATFORM=cpu run
        # stamps true so it can never be read as a TPU number
        "fallback": dev.platform == "cpu",
        "recall_ok": recall_ok,
        "oracle_recall_at_k": oracle_recall,
        "corpus_mode": corpus_mode,
        "device": f"{dev.platform} ({dev})",
        "n_docs": n_docs,
        "cpu_baseline_qps": round(cpu_qps, 2),
        "engine": engine,
        "kernel": best,
        "kernel_qps": kernel_qps,
        "kernels": results,
        "percolate": perc_record,
        "refresh_interleave": ri_record,
        "fault_recovery": fr_record,
        "impact_pruning": imp_record,
        "tail_tolerance": tt_record,
        "planner_fusion": pf_record,
        "multichip_lanes": mc_record,
    }

    # live telemetry stamp: the HBM ledger's per-component/per-index
    # occupancy (the BENCH_r06 chip capture reads device residency for
    # free from here) plus end-of-run windowed rates per attributed
    # node id ("_process" is unattributed module-level activity)
    if os.environ.get("BENCH_TELEMETRY", "1") == "1":
        from elasticsearch_tpu.observability import ledger as _led
        from elasticsearch_tpu.observability import (
            histograms as _hist_mod)
        tel_ids = sorted(set(_ts.node_ids()) |
                         set(_hist_mod.node_ids()) | {""})
        for nid in tel_ids:
            _ts.tick(nid, force=True)
        record["telemetry"] = {
            "device_memory": _led.global_snapshot(),
            "rates": {nid or "_process": _ts.rates(nid)
                      for nid in tel_ids},
        }
        # the whole run's program cost books: per-lane predicted vs
        # measured µs + the hottest programs — the cost observatory
        # record the chip capture reads residency/latency from
        record["program_costs"] = program_costs_snapshot(top=12)
        dm = record["telemetry"]["device_memory"]
        log(f"[bench] telemetry: HBM ledger "
            f"{dm['total_bytes']} bytes across {dm['entries']} "
            f"entries; components "
            + ", ".join(f"{c}={b}" for c, b in
                        dm["by_component"].items() if b))

    # analyzer cost is tracked like any other leg: stamp the wall time of
    # a full-tree plane-lint v2 run (whole-program pass) so regressions
    # in the lint gate's budget show up in artifacts, not just CI
    if os.environ.get("BENCH_LINT", "1") == "1":
        try:
            from elasticsearch_tpu.analysis.lint import lint_paths
            _lint_t0 = time.monotonic()
            _lint = lint_paths([os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "elasticsearch_tpu")])
            record["lint_wall_s"] = round(time.monotonic() - _lint_t0, 2)
            record["lint_open_findings"] = len(_lint.unsuppressed)
            log(f"[bench] plane-lint: {record['lint_wall_s']}s wall, "
                f"{record['lint_open_findings']} open finding(s)")
        except Exception as e:             # noqa: BLE001 — bench must record
            log(f"[bench] plane-lint leg failed ({e}); skipping stamp")

    print(json.dumps(record))
    # the parity check gates the metric: a fast-but-wrong result must not
    # be recorded as a pass
    return 0 if record["recall_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
