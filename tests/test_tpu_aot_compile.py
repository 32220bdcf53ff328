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
        eng.install_segment(Segment.from_packed_text(
            0, "body", terms=terms, tokens=None,
            uterms=np.zeros((ROWS, BM25_CELL_UNIQUE), np.int32),
            utf=np.zeros((ROWS, BM25_CELL_UNIQUE), np.float32),
            doc_len=np.ones(ROWS, np.int32), df=np.ones(VOCAB, np.int64),
            num_docs=ROWS, ids=[""] * ROWS), track_versions=False)
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
            eng.install_segment(Segment.from_packed_text(
                j, "body", terms=terms, tokens=None,
                uterms=np.zeros((PLANE_CELL_ROWS, BM25_CELL_UNIQUE),
                                np.int32),
                utf=np.zeros((PLANE_CELL_ROWS, BM25_CELL_UNIQUE),
                             np.float32),
                doc_len=np.ones(PLANE_CELL_ROWS, np.int32),
                df=np.ones(VOCAB, np.int64), num_docs=PLANE_CELL_ROWS,
                ids=[""] * PLANE_CELL_ROWS), track_versions=False)
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
    assert "all-gather" in text, "no cross-chip candidate merge"
    for scope in ("plane_score", "plane_select", "plane_gather",
                  "plane_merge", "bm25_score"):
        assert scope in text, scope
