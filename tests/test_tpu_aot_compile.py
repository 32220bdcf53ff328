"""Ahead-of-time compiles of the served path's programs for a DESCRIBED
TPU v5e (no chip attached): the TPU compiler refuses here — for free —
what it would refuse on the chip: programs whose intermediates do not
fit 16 GB of HBM at [B=64, 2^20 rows], top-k at k=1000 over 2^20 lanes,
the block-max sweep's gathers, and the mesh lanes' collectives.

Shapes are chip_smoke.py's: one 2^20-row bucketed segment at the 1M-doc
corpus's unique-term width, B=64; the opt-in lanes' index at 2^15 rows
with 768-dim vectors.

This must stay ONE file with the topology fixture inside it: only one
process may load the TPU library, pytest workers each import every test
file, and only the worker that is handed this file may describe the
topology — from inside a fixture, after a test of the file has started
(never at import, in a ``skipif`` or in a ``parametrize`` argument).

A compile that passes here is not a chip run and is never reported as
one.
"""

from __future__ import annotations

import contextlib
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.index.device_reader import device_reader_for
from elasticsearch_tpu.index.engine import Engine
from elasticsearch_tpu.index.segment import Segment, VectorFieldColumn
from elasticsearch_tpu.mapping import MapperService
from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.phase import (ShardSearcher,
                                            parse_search_request)

ROWS = 1 << 20            # chip_smoke's bucketed 1M-doc segment
UNIQUE = 36               # its unique-term width (seed 20240924)
VOCAB = 30_000
BATCH = 64
LANE_ROWS = 1 << 15       # the opt-in lanes' index
LANE_VOCAB = 2_000
VEC_DIMS = 768
HBM_BYTES = 16 * 1024 ** 3
# temp_size_in_bytes of the B=64, 4-term `reader-batch` program by k, at
# the parent of the PR that made bm25_match one pass (commit e62604a,
# this file's test_reader_batch_program run there; a compile for the
# described v5e, not a chip run)
PARENT_B64_TEMP_BYTES = {10: 2_685_644_800, 1000: 1_511_626_752}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # noqa: BLE001 — skip, don't fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


class _Captured(BaseException):
    """Carries the chip's ``Compiled`` out of the lane: it cannot run
    here, and a BaseException passes the lanes' ``except Exception →
    eager rescue`` seams untouched."""

    def __init__(self, compiled):
        super().__init__("compiled for the described chip")
        self.compiled = compiled


@contextlib.contextmanager
def steered_to(sharding_of, mesh=None):
    """Steer the lane code from the test: inside, every program
    ``jit_exec`` lowers is lowered for the described chip — its operands
    become ``ShapeDtypeStruct``s carrying ``sharding_of(operand)`` — and
    the compile result leaves through :class:`_Captured`. With ``mesh``,
    shard_map programs are built over it instead of the CPU mesh their
    operands were placed on. The program gets no option for this."""
    from elasticsearch_tpu.parallel import mesh as mesh_mod

    class Lowered:
        def __init__(self, lowered):
            self.lowered = lowered

        def compile(self):
            raise _Captured(self.lowered.compile())

    class Jitted:
        def __init__(self, fn, **kw):
            self.jitted = jax.jit(fn, **kw)

        def lower(self, *args):
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sharding_of(a)),
                args)
            return Lowered(self.jitted.lower(*shapes))

    proxy = types.SimpleNamespace(
        **{n: getattr(jax, n) for n in dir(jax) if not n.startswith("__")})
    proxy.jit = Jitted
    real_jax, real_sm = jit_exec.jax, mesh_mod.shard_map_compat
    jit_exec.jax = proxy
    if mesh is not None:
        described = mesh

        def on_described_mesh(f, mesh, in_specs, out_specs):
            return real_sm(f, mesh=described, in_specs=in_specs,
                           out_specs=out_specs)
        mesh_mod.shard_map_compat = on_described_mesh
    try:
        yield
    finally:
        jit_exec.jax = real_jax
        mesh_mod.shard_map_compat = real_sm


def captured(fn, *args, **kwargs):
    """Run ``fn`` until its program is compiled → that ``Compiled``."""
    with pytest.raises(_Captured) as exc:
        fn(*args, **kwargs)
    return exc.value.compiled


def fits_hbm(compiled, label: str):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    print(f"\n[aot] {label}: args {ma.argument_size_in_bytes / 2**20:.1f} "
          f"MiB, out {ma.output_size_in_bytes / 2**20:.1f} MiB, temp "
          f"{ma.temp_size_in_bytes / 2**20:.1f} MiB per device")
    assert need < HBM_BYTES, f"{label} needs {need} bytes of 16 GB HBM"
    return ma


def whole_row_selections(text: str, elements: int) -> list:
    """The compiled program's sorts and ``TopK`` calls whose operand holds
    ``elements`` scores or more: a top-k that ranks a whole segment's (or
    slot's) scores for every row of the batch. (XLA lays a top-k of
    [64, 2^20] out as a sort of [8192, 8192], so the minor dimension
    alone cannot tell.) Two stages rank block maxima and the chosen
    blocks."""
    size = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]", ln)
        if m:
            size[m.group(1)] = int(np.prod(
                [int(d) for d in m.group(2).split(",") if d]))
    out = []
    for ln in text.splitlines():
        if " sort(" in ln or 'custom_call_target="TopK"' in ln:
            m = re.search(r" (?:sort|custom-call)\(%([^,)\s]+)", ln)
            if m and size.get(m.group(1), 0) >= elements:
                out.append(ln)
    return out


def _engine(tmp_path, rows: int, vocab: int, unique: int, *,
            vec_dims: int = 0):
    """An engine holding one packed segment of the given shape (the
    values only have to be well-formed: nothing runs)."""
    rng = np.random.default_rng(7)
    ms = MapperService()
    props = {"body": {"type": "text", "analyzer": "whitespace"}}
    if vec_dims:
        props["vec"] = {"type": "dense_vector", "dims": vec_dims}
    ms.merge("_doc", {"properties": props})
    uterms = rng.integers(0, vocab, (rows, unique)).astype(np.int32)
    uterms.sort(axis=1)
    df = np.bincount(uterms.ravel(), minlength=vocab)
    w = len(str(vocab - 1))
    seg = Segment.from_packed_text(
        0, "body", terms=[f"t{i:0{w}d}" for i in range(vocab)],
        tokens=None, uterms=uterms,
        utf=np.ones((rows, unique), np.float32),
        doc_len=np.full(rows, unique, np.int32), df=df, num_docs=rows,
        ids=[""] * rows)
    if vec_dims:
        seg.vector_fields["vec"] = VectorFieldColumn(
            vecs=np.zeros((rows, vec_dims), np.float32),
            exists=np.ones(rows, bool), dims=vec_dims)
    eng = Engine(tmp_path, ms)
    eng.install_segment(seg, track_versions=False)
    return eng, ms


@pytest.fixture(scope="module")
def big(tmp_path_factory):
    eng, ms = _engine(tmp_path_factory.mktemp("aot_big"), ROWS, VOCAB,
                      UNIQUE)
    yield eng, ms
    eng.close()
    jit_exec.clear_cache()


@pytest.fixture(scope="module")
def lanes(tmp_path_factory):
    eng, ms = _engine(tmp_path_factory.mktemp("aot_lanes"), LANE_ROWS,
                      LANE_VOCAB, UNIQUE, vec_dims=VEC_DIMS)
    yield eng, ms
    eng.close()
    jit_exec.clear_cache()


def _match_reqs(vocab: int, size: int, *, batch: int = BATCH,
                terms: int = 4, **extra) -> list:
    w = len(str(vocab - 1))
    return [parse_search_request({
        "query": {"match": {"body": " ".join(
            f"t{(17 * i + 5 * j) % vocab:0{w}d}" for j in range(terms))}},
        "size": size, **extra}) for i in range(batch)]


@pytest.mark.parametrize("k", [10, 1000])
def test_flagship_bm25_topk_batch(topo, no_persistent_cache, k):
    """models/bm25.bm25_topk_batch — what __graft_entry__.entry()
    returns — at 2^20 rows × U, B=64."""
    from jax.sharding import SingleDeviceSharding
    from elasticsearch_tpu.models.bm25 import bm25_topk_batch
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = bm25_topk_batch.lower(
        spec((ROWS, UNIQUE), jnp.int32), spec((ROWS, UNIQUE), jnp.float32),
        spec((ROWS,), jnp.int32), spec((ROWS,), jnp.bool_),
        spec((BATCH, 4), jnp.int32), spec((BATCH, 4), jnp.float32),
        spec((), jnp.float32), k=k).compile()
    fits_hbm(compiled, f"bm25_topk_batch k={k}")


@pytest.mark.parametrize("k", [10, 1000])
def test_reader_batch_program(topo, no_persistent_cache, big, k):
    """The program the served BM25 path dispatches (`reader-batch`),
    reached through ShardSearcher.query_phase_batch as the scheduler
    reaches it."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = big
    chip = SingleDeviceSharding(topo.devices[0])
    searcher = ShardSearcher(0, device_reader_for(eng), ms)
    with steered_to(lambda a: chip):
        compiled = captured(searcher.query_phase_batch,
                            _match_reqs(VOCAB, k))
    ma = fits_hbm(compiled, f"reader-batch k={k}")
    # bm25_match keeps no [N, U] hit mask per query term
    assert ma.temp_size_in_bytes <= PARENT_B64_TEMP_BYTES[k]


@pytest.mark.parametrize("terms", [2, 12, 16])
def test_reader_batch_reads_columns_once(topo, no_persistent_cache, big,
                                         terms):
    """The B = 1, k = 1000 `reader-batch` program of a `match` query —
    what a lone search dispatches — reads the segment's forward columns
    once whatever the query's length: bm25_match is one pass over
    [N, U], not one (and more) per query term. 12 terms is the widest
    program the benchmark's traffic reaches, 16 the next term bucket."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = big
    chip = SingleDeviceSharding(topo.devices[0])
    searcher = ShardSearcher(0, device_reader_for(eng), ms)
    with steered_to(lambda a: chip):
        compiled = captured(searcher.query_phase_batch,
                            _match_reqs(VOCAB, 1000, batch=1, terms=terms))
    columns = ROWS * UNIQUE * (4 + 4)             # uterms int32 + utf f32
    accessed = compiled.cost_analysis()["bytes accessed"]
    print(f"\n[aot] reader-batch B=1 k=1000 T={terms}: bytes accessed "
          f"{accessed} = {accessed / columns:.3f} x the columns' {columns}")
    # one read of the columns, and what the program moves per ROW beside
    # them (doc_len, the live mask, the scores through top-k and packing),
    # which does not grow with U: 127 bytes a row at both lengths here
    assert accessed <= 1.25 * columns + 128 * ROWS


BM25_CELL_SEGMENTS = 4    # msmarco-bm25.msearch64-top1000: 4 x [2^20, 224]
BM25_CELL_UNIQUE = 224
# the slot plan of the cells' length-ordered segments, on every seed at
# 2^20 and at 1,105,920 rows (tests/test_slot_bounds.py)
CELL_SLOT_WIDTHS = (224, 112, 96, 80, 80, 64, 64, 64, 64, 48, 48, 48, 48,
                    48, 32, 32)


def with_cell_slot_plan(seg: Segment) -> Segment:
    """Give a segment of ``np.zeros`` columns the slot plan of the cells'
    lengths: zeros read as a term in every slot, and columns that hold
    the pads past each length would back 3.7 GB of pages a segment."""
    from elasticsearch_tpu.ops import lexical
    with pytest.MonkeyPatch.context() as m:
        m.setattr(lexical, "plan_slots", lambda u: lexical.SlotPlan(
            u.shape[0], CELL_SLOT_WIDTHS))
        plan = seg.text_fields["body"].slot_plan    # read once, then kept
    assert plan.widths == CELL_SLOT_WIDTHS
    return seg


def bounded_passes(text: str, rows: int) -> list:
    """The (rows, width) of each run of the cell plan's chunks that the
    compiled program holds no trace of."""
    from elasticsearch_tpu.ops.lexical import SlotPlan
    return [(e - a, w) for a, e, w in SlotPlan(rows, CELL_SLOT_WIDTHS).runs()
            if f"{e - a},{w}]" not in text]


@contextlib.contextmanager
def reader_uploads_as_shapes():
    """Nothing large is copied to a device: inside, the reader's upload
    of a column hands back its shape, which is all a lowering reads
    (7.5 GB of zeros stay unbacked pages)."""
    proxy = types.SimpleNamespace(
        **{n: getattr(jax, n) for n in dir(jax) if not n.startswith("__")})
    proxy.device_put = lambda a, *args, **kw: (
        jax.ShapeDtypeStruct(a.shape, a.dtype) if a.nbytes > 1 << 24
        else jax.device_put(a, *args, **kw))
    real = jit_exec.jax
    jit_exec.jax = proxy
    try:
        yield
    finally:
        jit_exec.jax = real


@pytest.fixture(scope="module")
def bm25_cell(tmp_path_factory):
    """The benchmark configuration ``msmarco-passage-bm25`` at its own
    shapes: four packed [2^20, 224] segments. The columns are untouched
    ``np.zeros`` (pages the kernel never backs: nothing writes them)."""
    ms = MapperService()
    ms.merge("_doc", {"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    eng = Engine(tmp_path_factory.mktemp("aot_bm25_cell"), ms)
    w = len(str(VOCAB - 1))
    terms = [f"t{i:0{w}d}" for i in range(VOCAB)]
    for _ in range(BM25_CELL_SEGMENTS):
        eng.install_segment(with_cell_slot_plan(Segment.from_packed_text(
            0, "body", terms=terms, tokens=None,
            uterms=np.zeros((ROWS, BM25_CELL_UNIQUE), np.int32),
            utf=np.zeros((ROWS, BM25_CELL_UNIQUE), np.float32),
            doc_len=np.ones(ROWS, np.int32), df=np.ones(VOCAB, np.int64),
            num_docs=ROWS, ids=[""] * ROWS)), track_versions=False)
    yield eng, ms
    eng.close()
    jit_exec.clear_cache()


def test_mixed_msearch_at_the_benchmark_cells_shapes(
        topo, no_persistent_cache, bm25_cell):
    """A request of ``msmarco-bm25.msearch64-top1000`` — 64 `match`
    queries of 2 to 12 terms, size 1000, over 4 x [2^20, 224] — plans to
    ONE `reader-batch` program (term lists padded to 12), which compiles
    for the chip; two of them (two clients keep two enqueued) fit HBM
    with the resident columns counted once."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = bm25_cell
    chip = SingleDeviceSharding(topo.devices[0])
    with reader_uploads_as_shapes():
        searcher = ShardSearcher(0, device_reader_for(eng), ms)
    w = len(str(VOCAB - 1))
    lengths = [2 + (7 * i) % 11 for i in range(BATCH)]
    assert set(lengths) == set(range(2, 13))
    reqs = [parse_search_request({
        "query": {"match": {"body": " ".join(
            f"t{(17 * i + 5 * j) % VOCAB:0{w}d}" for j in range(ln))}},
        "size": 1000}) for i, ln in enumerate(lengths)]
    before = jit_exec.cache_stats()["misses"]
    with steered_to(lambda a: chip):
        compiled = captured(searcher.query_phase_batch, reqs)
    assert jit_exec.cache_stats()["misses"] - before == 1
    ma = fits_hbm(compiled, "mixed msearch64 k=1000, 4 x [2^20, 224]")
    columns = BM25_CELL_SEGMENTS * ROWS * BM25_CELL_UNIQUE * (4 + 4)
    assert ma.argument_size_in_bytes >= columns
    assert ma.argument_size_in_bytes + 2 * (
        ma.temp_size_in_bytes + ma.output_size_in_bytes) < HBM_BYTES
    # each segment's top 1000 in two stages: no sort of a whole row
    assert whole_row_selections(compiled.as_text(), BATCH * ROWS) == []
    # each run of the slot plan's chunks scored to its own width
    assert bounded_passes(compiled.as_text(), ROWS) == []
    cost = compiled.cost_analysis()
    print(f"[aot] mixed msearch64: flops {cost['flops']:.4g}, bytes "
          f"accessed {cost['bytes accessed']:.4g}")


def test_impact_pruned_sweep(topo, no_persistent_cache, big):
    """The block-max pruned sweep (ops/blockmax.py) over the 2^20-row
    impact columns and their [512, 30000] block-max table."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = big
    chip = SingleDeviceSharding(topo.devices[0])
    jit_exec.configure_impact_plane(
        "aot", {"index.search.impact_plane": "true"})
    try:
        searcher = ShardSearcher(0, device_reader_for(eng), ms,
                                 index_name="aot")
        with steered_to(lambda a: chip):
            compiled = captured(
                searcher.query_phase_batch,
                _match_reqs(VOCAB, 10, track_total_hits=False))
    finally:
        jit_exec.configure_impact_plane("aot", {})
    fits_hbm(compiled, "impact-pruned k=10")
    assert "while" in compiled.as_text()      # the block sweep's loop


def test_knn_program_768(topo, no_persistent_cache, lanes):
    from jax.sharding import SingleDeviceSharding
    eng, ms = lanes
    chip = SingleDeviceSharding(topo.devices[0])
    searcher = ShardSearcher(0, device_reader_for(eng), ms)
    reqs = [parse_search_request({
        "knn": {"field": "vec", "k": 10, "num_candidates": 100,
                "query_vector": [float(i + 1)] * VEC_DIMS},
        "size": 10}) for i in range(4)]
    with steered_to(lambda a: chip):
        compiled = captured(searcher.query_phase_batch, reqs)
    fits_hbm(compiled, "knn 768 dims")


KNN_CELL_SEGMENTS = 3     # dense768-knn.search-k10-c16: 3 x [2^20, 768]


@pytest.fixture(scope="module")
def knn_cell(tmp_path_factory):
    """The benchmark configuration ``dense768-cosine-knn`` at its own
    shapes: three packed [2^20, 768] float32 segments. The columns are
    untouched ``np.zeros`` (pages the kernel never backs: nothing writes
    them)."""
    ms = MapperService()
    ms.merge("_doc", {"properties": {
        "emb": {"type": "dense_vector", "dims": VEC_DIMS}}})
    eng = Engine(tmp_path_factory.mktemp("aot_knn_cell"), ms)
    for _ in range(KNN_CELL_SEGMENTS):
        eng.install_segment(Segment.from_packed_vectors(
            0, "emb", np.zeros((ROWS, VEC_DIMS), np.float32),
            np.ones(ROWS, bool), ROWS, ids=[""] * ROWS),
            track_versions=False)
    yield eng, ms
    eng.close()
    jit_exec.clear_cache()


@contextlib.contextmanager
def uploads_as_shapes():
    """Nothing is copied to a device: inside, the vector block cache's
    upload hands back shapes, which is all a lowering reads (9.66 GB of
    zeros stay unbacked pages)."""
    from elasticsearch_tpu.parallel import mesh_engine
    proxy = types.SimpleNamespace(
        **{n: getattr(jax, n) for n in dir(jax) if not n.startswith("__")})
    proxy.device_put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    real = mesh_engine.jax
    mesh_engine.jax = proxy
    try:
        yield
    finally:
        mesh_engine.jax = real


@pytest.mark.parametrize("b_pad", [1, 2, 4, 8, 16])
def test_knn_program_at_the_benchmark_cells_shapes(
        topo, no_persistent_cache, knn_cell, b_pad):
    """The knn program of ``dense768-knn.search-k10-c16`` — 3 x [2^20,
    768] float32, k 10, num_candidates 100 — at every batch bucket its 16
    clients can fill: it compiles for the chip, two of them fit HBM side
    by side with the resident vectors counted once, and its product is
    the six-pass one (nothing under ``knn_score`` touches bfloat16)."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = knn_cell
    chip = SingleDeviceSharding(topo.devices[0])
    searcher = ShardSearcher(0, device_reader_for(eng), ms)
    reqs = [parse_search_request({
        "knn": {"field": "emb", "k": 10, "num_candidates": 100,
                "query_vector": [float(i + 1)] * VEC_DIMS},
        "size": 10}) for i in range(b_pad)]
    with steered_to(lambda a: chip), uploads_as_shapes():
        compiled = captured(searcher.query_phase_batch, reqs)
    ma = fits_hbm(compiled, f"knn cell 3 x [2^20, 768] B_pad={b_pad}")
    vectors = KNN_CELL_SEGMENTS * ROWS * VEC_DIMS * 4
    assert ma.argument_size_in_bytes >= vectors
    assert vectors + 2 * (ma.temp_size_in_bytes
                          + ma.output_size_in_bytes) < HBM_BYTES
    text = compiled.as_text()
    products = [ln for ln in text.splitlines()
                if " convolution(" in ln and "knn_score" in ln]
    # one MXU product a segment (at the smallest buckets the compiler may
    # make it a float32 multiply-and-reduce on the VPU instead: exact too)
    assert len(products) == KNN_CELL_SEGMENTS or b_pad < 4
    assert all("operand_precision={highest,highest}" in ln
               for ln in products)
    assert "bf16" not in "".join(
        ln for ln in text.splitlines() if "knn_score" in ln)
    assert "knn_select" in text and "knn_merge" in text
    # the selection lowers as the knn lane's own form did before the
    # rule and the two stages were shared with every lane
    from elasticsearch_tpu.ops import vector as vector_ops
    rows = [jax.ShapeDtypeStruct((b_pad, ROWS), dt, sharding=chip)
            for dt in (jnp.float32, jnp.bool_)]

    def lowered(select):
        return jax.jit(lambda s, m: select(s, m, 100)).lower(*rows).as_text()
    assert lowered(vector_ops.filtered_topk_batch) == \
        lowered(_knn_selection_for_b_n_rows)


def _knn_selection_for_b_n_rows(scores, masks, k: int, doc_base: int = 0):
    """``ops/vector.filtered_topk_batch`` as written for ``[B, N]`` rows
    alone, with its own block rule (N a multiple of the block)."""
    neg_inf = jnp.float32(-jnp.inf)
    masked = jnp.where(masks, scores, neg_inf)
    b, n = masked.shape
    block = 128
    while block * block * 4 * k <= n:
        block *= 2
    blocks = masked.reshape(b, n // block, block)
    _, bid = jax.lax.top_k(blocks.max(axis=2), k)
    bid = jnp.sort(bid, axis=1)
    cand = jnp.take_along_axis(blocks, bid[:, :, None], axis=1)
    ts, pos = jax.lax.top_k(cand.reshape(b, k * block), k)
    idx = jnp.take_along_axis(bid, pos // block, axis=1) * block \
        + pos % block
    valid = ts > neg_inf
    td = jnp.where(valid, idx.astype(jnp.int32) + doc_base, -1)
    return jnp.where(valid, ts, neg_inf), td


def test_impact_mesh_program_1x4(topo, no_persistent_cache, big):
    """One mesh program on four described chips, geometry (1,4): the
    per-shard sweeps must merge through cross-chip collectives."""
    from jax.sharding import NamedSharding
    from elasticsearch_tpu.parallel.mesh import make_mesh
    eng, ms = big
    cpu_mesh = make_mesh(dp=1, shard=4, devices=jax.devices()[:4])
    tpu_mesh = make_mesh(dp=1, shard=4, devices=topo.devices)
    jit_exec.configure_impact_plane(
        "aot", {"index.search.impact_plane": "true"})
    jit_exec.set_serving_mesh(cpu_mesh)
    try:
        searcher = ShardSearcher(0, device_reader_for(eng), ms,
                                 index_name="aot")
        with steered_to(lambda a: NamedSharding(tpu_mesh, a.sharding.spec),
                        mesh=tpu_mesh):
            compiled = captured(
                searcher.query_phase_batch,
                _match_reqs(VOCAB, 10, track_total_hits=False))
    finally:
        jit_exec.set_serving_mesh(None)
        jit_exec.configure_impact_plane("aot", {})
    ma = fits_hbm(compiled, "impact-mesh (1,4) k=10")
    # the doc axis really is split: a quarter of the columns per chip
    assert ma.argument_size_in_bytes < ROWS * UNIQUE * 5 // 2
    text = compiled.as_text()
    assert "all-gather" in text, "no cross-chip candidate merge"
    assert "all-reduce" in text, "no cross-chip count/theta reduction"



# ---------------------------------------------------------------------------
# the collective plane at the four-chip cell's shapes
# ---------------------------------------------------------------------------

PLANE_CELL_SHARDS = 4          # msmarco-bm25-4shard.msearch64-top1000-4chip:
PLANE_CELL_SHARD_SEGMENTS = 2  # 4 shards x 2 x [1,105,920, 224], one shard
PLANE_CELL_ROWS = 1_105_920    # a chip of a (1,4) mesh


@pytest.fixture(scope="module")
def plane_cell(tmp_path_factory):
    """The configuration ``msmarco-passage-bm25-4shard`` at its own
    shapes: four engines (shards) of two packed segments each. The
    columns are untouched ``np.zeros`` (15.9 GB of pages nothing backs:
    the pack takes a column of the layout's shape as it is)."""
    ms = MapperService()
    ms.merge("_doc", {"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    w = len(str(VOCAB - 1))
    terms = [f"t{i:0{w}d}" for i in range(VOCAB)]
    engines = []
    for s in range(PLANE_CELL_SHARDS):
        eng = Engine(tmp_path_factory.mktemp(f"aot_plane_{s}"), ms)
        for j in range(PLANE_CELL_SHARD_SEGMENTS):
            eng.install_segment(with_cell_slot_plan(Segment.from_packed_text(
                j, "body", terms=terms, tokens=None,
                uterms=np.zeros((PLANE_CELL_ROWS, BM25_CELL_UNIQUE),
                                np.int32),
                utf=np.zeros((PLANE_CELL_ROWS, BM25_CELL_UNIQUE),
                             np.float32),
                doc_len=np.ones(PLANE_CELL_ROWS, np.int32),
                df=np.ones(VOCAB, np.int64), num_docs=PLANE_CELL_ROWS,
                ids=[""] * PLANE_CELL_ROWS)), track_versions=False)
        engines.append(eng)
    yield engines, ms
    for eng in engines:
        eng.close()
    from elasticsearch_tpu.parallel import mesh_engine
    mesh_engine.clear_program_cache()
    mesh_engine.clear_block_cache()


@contextlib.contextmanager
def plane_steered_to(described):
    """Steer ``parallel/mesh_engine`` from the test, as :func:`steered_to`
    steers ``jit_exec``: a block's upload of a large column hands back
    its shape, operands assembled from such blocks are shapes with their
    sharding, the shard_map is built over the ``described`` mesh, and the
    program is lowered for it — its ``Compiled`` leaves through
    :class:`_Captured`."""
    from jax.sharding import NamedSharding
    from elasticsearch_tpu.parallel import mesh as mesh_mod
    from elasticsearch_tpu.parallel import mesh_engine

    def device_put(a, *args, **kw):
        if getattr(a, "nbytes", 0) > 1 << 24:
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return jax.device_put(a, *args, **kw)

    def make_array(shape, sharding, bufs):
        if any(isinstance(b, jax.ShapeDtypeStruct) for b in bufs):
            return jax.ShapeDtypeStruct(shape, bufs[0].dtype,
                                        sharding=sharding)
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        bufs)

    class Lowered:
        def __init__(self, lowered):
            self.lowered = lowered

        def compile(self):
            raise _Captured(self.lowered.compile())

    class Jitted:
        def __init__(self, fn, **kw):
            self.jitted = jax.jit(fn, **kw)

        def lower(self, *args):
            return Lowered(self.jitted.lower(*jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=NamedSharding(described, a.sharding.spec)),
                args)))

    proxy = types.SimpleNamespace(
        **{n: getattr(jax, n) for n in dir(jax) if not n.startswith("__")})
    proxy.device_put = device_put
    proxy.make_array_from_single_device_arrays = make_array
    proxy.jit = Jitted
    real_jax, real_sm = mesh_engine.jax, mesh_mod.shard_map_compat
    mesh_engine.jax = proxy
    mesh_mod.shard_map_compat = lambda f, mesh, in_specs, out_specs: \
        real_sm(f, mesh=described, in_specs=in_specs, out_specs=out_specs)
    try:
        yield
    finally:
        mesh_engine.jax = real_jax
        mesh_mod.shard_map_compat = real_sm


def test_plane_program_at_the_4shard_cells_shapes(
        topo, no_persistent_cache, plane_cell):
    """A request of ``msmarco-bm25-4shard.msearch64-top1000-4chip`` — 64
    `match` queries of 2 to 12 terms, size 1000, over 4 shards x 2 x
    [1,105,920, 224] on a (1,4) mesh — plans to ONE plane program (term
    lists padded to 12, k bucket 1024) that compiles for four described
    chips: a quarter of the columns a device, the shards' candidates
    met by an all-gather, and two programs in flight (two clients) fit
    HBM beside the resident columns."""
    from elasticsearch_tpu.parallel.mesh import make_mesh
    from elasticsearch_tpu.parallel.mesh_engine import MeshEngineSearcher
    engines, ms = plane_cell
    cpu_mesh = make_mesh(dp=1, shard=4, devices=jax.devices()[:4])
    tpu_mesh = make_mesh(dp=1, shard=4, devices=topo.devices)
    w = len(str(VOCAB - 1))
    lengths = [2 + (7 * i) % 11 for i in range(BATCH)]
    bodies = [{"query": {"match": {"body": " ".join(
        f"t{(17 * i + 5 * j) % VOCAB:0{w}d}" for j in range(ln))}},
        "size": 1000} for i, ln in enumerate(lengths)]
    before = jit_exec.cache_stats()["mesh_program_misses"]
    with plane_steered_to(tpu_mesh):
        searcher = MeshEngineSearcher(cpu_mesh, engines, ms)
        assert searcher.spd == 1 and searcher.n_slots == 2
        compiled = captured(searcher.search_batch, bodies,
                            global_stats=False)
    assert jit_exec.cache_stats()["mesh_program_misses"] - before == 1
    ma = fits_hbm(compiled, "plane (1,4) mixed msearch64 k=1024, "
                            "2 x [1105920, 224] a device")
    shard_columns = PLANE_CELL_SHARD_SEGMENTS * PLANE_CELL_ROWS \
        * BM25_CELL_UNIQUE * (4 + 4)
    # a device holds ITS shard's columns and no other's
    assert shard_columns <= ma.argument_size_in_bytes < 1.1 * shard_columns
    assert ma.argument_size_in_bytes + 2 * (
        ma.temp_size_in_bytes + ma.output_size_in_bytes) < HBM_BYTES
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_local"), text[:80]
    assert whole_row_selections(text, BATCH * PLANE_CELL_ROWS) == []
    assert "all-gather" in text, "no cross-chip candidate merge"
    # each shard's slots scored run by run under their merged slot plan
    assert bounded_passes(text, PLANE_CELL_ROWS) == []
    for scope in ("plane_score", "plane_select", "plane_gather",
                  "plane_merge", "bm25_score"):
        assert scope in text, scope


PHRASE_CELL_SEGMENTS = 4  # msmarco-phrase.bool-phrase-top10-c16


@pytest.fixture(scope="module")
def phrase_cell(tmp_path_factory):
    """The benchmark configuration ``msmarco-passage-bm25-phrase`` at its
    own shapes: four packed [2^20, 224] segments WITH their [2^20, 224]
    position matrices (untouched ``np.zeros``, as ``bm25_cell``'s)."""
    ms = MapperService()
    ms.merge("_doc", {"properties": {
        "body": {"type": "text", "analyzer": "whitespace"}}})
    eng = Engine(tmp_path_factory.mktemp("aot_phrase_cell"), ms)
    w = len(str(VOCAB - 1))
    terms = [f"t{i:0{w}d}" for i in range(VOCAB)]
    for _ in range(PHRASE_CELL_SEGMENTS):
        eng.install_segment(with_cell_slot_plan(Segment.from_packed_text(
            0, "body", terms=terms,
            tokens=np.zeros((ROWS, BM25_CELL_UNIQUE), np.int32),
            uterms=np.zeros((ROWS, BM25_CELL_UNIQUE), np.int32),
            utf=np.zeros((ROWS, BM25_CELL_UNIQUE), np.float32),
            doc_len=np.ones(ROWS, np.int32), df=np.ones(VOCAB, np.int64),
            num_docs=ROWS, ids=[""] * ROWS)), track_versions=False)
    yield eng, ms
    eng.close()
    jit_exec.clear_cache()


def test_phrase_program_at_the_benchmark_cells_shapes(
        topo, no_persistent_cache, phrase_cell):
    """A batch of 16 bodies of ``msmarco-phrase.bool-phrase-top10-c16`` —
    bool must match (msm 30%) + should match_phrase, 9 to 12 terms (the
    widest term bucket), size 10, over 4 x [2^20, 224] columns and
    positions — plans to ONE `reader-batch` program, which compiles for
    the chip and fits HBM twice beside the resident columns: the phrase
    pass keeps no [B, N, L] array."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = phrase_cell
    chip = SingleDeviceSharding(topo.devices[0])
    w = len(str(VOCAB - 1))
    reqs = []
    for i in range(16):
        text = " ".join(f"t{(17 * i + 5 * j) % VOCAB:0{w}d}"
                        for j in range(9 + i % 4))
        reqs.append(parse_search_request({"query": {"bool": {
            "must": [{"match": {"body": {
                "query": text, "minimum_should_match": "30%"}}}],
            "should": [{"match_phrase": {"body": text}}]}}, "size": 10}))
    before = jit_exec.cache_stats()["misses"]
    with reader_uploads_as_shapes():
        searcher = ShardSearcher(0, device_reader_for(eng), ms)
        with steered_to(lambda a: chip):
            compiled = captured(searcher.query_phase_batch, reqs)
    assert jit_exec.cache_stats()["misses"] - before == 1
    ma = fits_hbm(compiled, "phrase cell B=16 k=10, 4 x [2^20, 224] x 3")
    columns = PHRASE_CELL_SEGMENTS * ROWS * BM25_CELL_UNIQUE * (4 + 4 + 4)
    assert ma.argument_size_in_bytes >= columns
    assert ma.argument_size_in_bytes + 2 * (
        ma.temp_size_in_bytes + ma.output_size_in_bytes) < HBM_BYTES
    assert "phrase_match" in compiled.as_text()
    assert whole_row_selections(compiled.as_text(), 16 * ROWS) == []
    assert bounded_passes(compiled.as_text(), ROWS) == []


@pytest.mark.parametrize("deltas", [tuple(range(12)), (0, 2, 3)])
def test_phrase_kernel_at_the_benchmark_cells_shapes(topo,
                                                     no_persistent_cache,
                                                     deltas):
    """The exact-phrase kernel alone over one [2^20, 224] position matrix,
    B = 16: consecutive terms (the padded phrase) and a phrase with a gap
    (a stop filter) are one `tpu_custom_call` each, the transposed matrix
    is a bitcast of the one on the device (no copy), and nothing of
    [B, N, L] or a shifted copy is kept."""
    from jax.sharding import SingleDeviceSharding
    from elasticsearch_tpu.ops import phrase as phrase_ops
    chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(
        lambda t, q: phrase_ops._phrase_counts_tpu(t, q, deltas)).lower(
        jax.ShapeDtypeStruct((ROWS, BM25_CELL_UNIQUE), jnp.int32,
                             sharding=chip),
        jax.ShapeDtypeStruct((16, len(deltas)), jnp.int32,
                             sharding=chip)).compile()
    ma = fits_hbm(compiled, f"phrase kernel deltas={deltas}")
    assert ma.temp_size_in_bytes < ROWS * BM25_CELL_UNIQUE * 4 // 8
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not [ln for ln in text.splitlines()
                if str(ROWS) in ln and (" copy(" in ln or " transpose(" in ln)]


GEONAMES_SEGMENTS = 11      # geonames-fscore.fscore-mix-top10-c16
GEONAMES_DOCS = 11_396_503
GEONAMES_NAME_SLOTS = 16


#: the keyword and coded text fields of Rally geonames' mapping (their
#: columns are resident beside the ones the functions read)
GEONAMES_RAW = ("name", "asciiname", "alternatenames", "feature_class",
                "feature_code", "country_code", "cc2", "admin1_code",
                "admin2_code", "admin3_code", "admin4_code", "dem",
                "timezone")


def _packed_text(terms, width: int) -> dict:
    return {"terms": terms, "tokens": np.zeros((ROWS, width), np.int32),
            "uterms": np.zeros((ROWS, width), np.int32),
            "utf": np.zeros((ROWS, width), np.float32),
            "doc_len": np.ones(ROWS, np.int32),
            "df": np.ones(len(terms), np.int64)}


@pytest.fixture(scope="module")
def geonames_cell(tmp_path_factory):
    """The benchmark configuration ``geonames-function-score`` at its own
    shapes: eleven packed segments of 2^20 rows (the last one partly
    real) with every column of Rally's mapping — ``name`` and
    ``asciiname`` [2^20, 16], the coded text fields one or two slots, a
    ``.raw`` keyword each, ``population`` / ``geonameid`` / ``elevation``
    and ``location``."""
    import json
    config = json.loads(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "configs",
        "geonames-function-score.json")).read())
    ms = MapperService()
    ms.merge("_doc", config["index"]["mappings"]["_doc"])
    eng = Engine(tmp_path_factory.mktemp("aot_geonames_cell"), ms)
    terms = tuple(f"t{i:05d}" for i in range(VOCAB))
    width = GEONAMES_NAME_SLOTS
    for si in range(GEONAMES_SEGMENTS):
        n = min(ROWS, GEONAMES_DOCS - si * ROWS)
        exists = np.arange(ROWS) < n
        values = np.zeros(ROWS, np.float64)
        name = _packed_text(terms, width)
        eng.install_segment(Segment.from_packed_text(
            0, "name", **name, num_docs=n, ids=[""] * ROWS,
            numeric={f: (values, exists)
                     for f in ("population", "geonameid", "elevation")},
            geo={"location": (values, values, exists)},
            keyword={f"{f}.raw": (("a",), np.full((ROWS, 1), -1, np.int32))
                     for f in GEONAMES_RAW},
            text={"asciiname": name,
                  **{f: _packed_text(("a", "b"), 2 if f == "timezone"
                                     else 1)
                     for f in GEONAMES_RAW[3:]}}),
            track_versions=False)
    yield eng, ms
    eng.close()
    jit_exec.clear_cache()


@pytest.mark.parametrize("op", ["gauss", "script_score"])
def test_function_score_program_at_the_benchmark_cells_shapes(
        topo, no_persistent_cache, geonames_cell, op):
    """A batch of 16 of Rally geonames' geo ``gauss`` decays (an origin
    each) and of its lang-expression ``script_score`` over 11 x 2^20
    places plans to ONE `reader-batch` program, which compiles for the
    chip and fits HBM beside the resident columns, and ranks each
    segment's scores in two stages (no top-k of a whole [16, 2^20])."""
    from jax.sharding import SingleDeviceSharding
    eng, ms = geonames_cell
    chip = SingleDeviceSharding(topo.devices[0])
    script = ("abs(ln(abs(doc['population']) + 1) + doc['location'].lon "
              "+ doc['location'].lat) * _score")
    reqs = []
    for i in range(16):
        fn = {"gauss": {"location": {
            "origin": f"{-40 + 5 * i:.5f}, {7.5 * i:.5f}",
            "scale": "500km", "offset": "0km", "decay": 0.1}}} \
            if op == "gauss" else {"functions": [{"script_score": {
                "script": {"lang": "expression", "inline": script}}}]}
        reqs.append(parse_search_request({"query": {"function_score": {
            "query": {"match_all": {}}, **fn}}, "size": 10}))
    before = jit_exec.cache_stats()["misses"]
    with reader_uploads_as_shapes():
        searcher = ShardSearcher(0, device_reader_for(eng), ms)
        with steered_to(lambda a: chip):
            compiled = captured(searcher.query_phase_batch, reqs)
    assert jit_exec.cache_stats()["misses"] - before == 1
    ma = fits_hbm(compiled, f"geonames {op} B=16 k=10, 11 x 2^20 places")
    assert ma.argument_size_in_bytes + 2 * (
        ma.temp_size_in_bytes + ma.output_size_in_bytes) < HBM_BYTES
    assert whole_row_selections(compiled.as_text(), 16 * ROWS) == []
