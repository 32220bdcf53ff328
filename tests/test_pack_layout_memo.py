"""The reader-batch pack's per-segment memo (``DeviceSegment.pack_memo``),
on a small whole-record geonames index of three segments, on the CPU:

* a segment's program layout key is built once a reader generation
  (``pack_layout_memo_misses``) and reused by every later batch
  (``pack_layout_memo_hits``), and equals the column walk it replaces;
* a refresh packs new segments, which build their own keys, equal to the
  old ones where the layout did not change: the same programs serve;
* lazy materialization of a text field's positions keeps the key, and
  the memoised flat holds the device array;
* segments without ``lazy_put`` and ``dataclasses.replace`` copies keep
  no memo of another segment's arrays;
* racing first callers build a segment's key once;
* the three scoring operations answer as the reference does, before
  and after the memo engages.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
from dataclasses import replace as dc_replace
from pathlib import Path

import jax
import numpy as np
import pytest

from elasticsearch_tpu.index.device_reader import device_reader_for
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.search import jit_exec

REPO = Path(__file__).resolve().parent.parent
CELL = "geonames-fscore.fscore-mix-top10-c16"


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gn = _load(REPO / "benchmarks" / "corpora" / "geonames.py",
           "bench_geonames_for_memo")
CONFIG = json.loads((REPO / "benchmarks" / "configs"
                     / "geonames-function-score.json").read_text())
LIMITS = json.loads((REPO / "benchmarks" / "workloads"
                     / f"{CELL}.json").read_text())["limits"]
REQUEST = json.loads((REPO / "benchmarks" / "traffic"
                      / "fscore-mix-top10-c16.json").read_text()
                     )["streams"][0]["request"]
WHOLE = {**CONFIG, "corpus": {**CONFIG["corpus"], "segments": 3,
                              "segment_rows": 512, "docs": 1200,
                              "clusters": 16, "vocab": 600}}
FVF = gn.request_body([gn.FVF, None], 10)


@pytest.fixture(scope="module")
def corpus():
    return gn.generate(WHOLE, 11)


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    jit_exec.clear_cache()
    n = Node({"cluster.routing.allocation.disk.watermark.low": "1.0"},
             data_path=tmp_path_factory.mktemp("memo") / "n").start()
    yield n
    n.close()
    jit_exec.clear_cache()


_made = iter(range(1 << 30))


@pytest.fixture
def index(node, corpus):
    """A fresh index of the corpus, its reader not yet packed."""
    name = f"geonames-{next(_made)}"
    node.indices_service.create_index(name, CONFIG["index"])
    gn.install(corpus, node, name)
    return name


def _reader(node, index):
    return device_reader_for(node.indices_service.indices[index].engine(0))


def _memo_counts() -> tuple:
    st = jit_exec.cache_stats()
    return st["pack_layout_memo_hits"], st["pack_layout_memo_misses"]


def test_misses_count_segments_and_hits_count_reuse(node, index):
    """N batches on one reader generation: one walk a segment, then
    (N − 1) reuses a segment; the keys are the walks' values, the same
    object on every call, and ``_nodes/stats`` ``indices.jit`` shows both
    counters."""
    hits0, misses0 = _memo_counts()
    n = 4
    for _ in range(n):
        node.search(index, FVF)
    segments = _reader(node, index).segments
    assert len(segments) == 3
    hits1, misses1 = _memo_counts()
    assert misses1 - misses0 == len(segments)
    assert hits1 - hits0 == (n - 1) * len(segments)
    for seg in segments:
        key = seg.pack_memo["layout"]
        assert key == jit_exec._layout_walk(seg)
        assert jit_exec.layout_key(seg) is key
    jit = node.local_node_stats()["indices"]["jit"]
    assert jit["pack_layout_memo_misses"] >= len(segments)
    assert jit["pack_layout_memo_hits"] >= (n - 1) * len(segments)


def test_a_refresh_builds_new_keys_equal_where_the_layout_holds(node,
                                                                 index):
    """A new document and a refresh pack a new generation: each of its
    segments is a new object that walks its columns once, and the three
    that hold the installed places give the keys the old generation's
    gave, so their part of every program key is unchanged."""
    node.search(index, FVF)
    old = _reader(node, index)
    old_keys = [seg.pack_memo["layout"] for seg in old.segments]
    node.index_doc(index, "extra", {"name": "extra place",
                                    "population": 3,
                                    "location": [4.9, 52.4]})
    node.broadcast_actions.refresh(index)
    _hits0, misses0 = _memo_counts()
    reply = node.search(index, FVF)
    assert reply["hits"]["total"] == WHOLE["corpus"]["docs"] + 1
    new = _reader(node, index)
    assert new.generation != old.generation
    assert len(new.segments) == len(old.segments) + 1
    assert not any(a is b for a in new.segments for b in old.segments)
    assert _memo_counts()[1] - misses0 == len(new.segments)
    assert [seg.pack_memo["layout"] for seg in new.segments[:3]] \
        == old_keys
    assert new.segments[3].pack_memo["layout"] \
        == jit_exec._layout_walk(new.segments[3])
    for seg, key in zip(old.segments, old_keys):
        assert seg.pack_memo["layout"] is key


def test_lazy_positions_keep_the_key_and_the_flat_holds_them(node, index):
    """A phrase query uploads ``name``'s positions on first use: the key
    built before stays the walk's value, and the flat memoised for the
    phrase plan holds the device array now on the column."""
    node.search(index, FVF)
    seg = _reader(node, index).segments[0]
    key = seg.pack_memo["layout"]
    assert isinstance(seg.text["name"].tokens, np.ndarray)
    word = next(t for t in seg.text["name"].column.terms if t)
    before = jit_exec.cache_stats()
    reply = node.search(index, {"query": {"match_phrase": {
        "name": f"{word} {word}"}}, "size": 10})
    assert "hits" in reply
    assert jit_exec.cache_stats()["phrase_items_batched"] \
        > before["phrase_items_batched"]
    tokens = seg.text["name"].tokens
    assert isinstance(tokens, jax.Array)
    assert jit_exec.layout_key(seg) is key
    assert key == jit_exec._layout_walk(seg)
    flat = seg.pack_memo[("flat", frozenset({"name"}), frozenset())]
    assert any(a is tokens for a in flat)
    assert all(a is b for a, b in zip(
        flat, jit_exec._flatten_walk(seg, frozenset({"name"}),
                                     frozenset())))
    assert jit_exec.seg_flatten(seg, frozenset({"name"}),
                                frozenset()) is flat


def test_host_segments_and_copies_keep_no_memo_of_others(node, index):
    """A segment without ``lazy_put`` (a mesh template, a streamed host
    pool) is walked on every flatten; a ``dataclasses.replace`` copy
    starts with an empty memo and builds its own key, equal in value."""
    node.search(index, FVF)
    seg = _reader(node, index).segments[1]
    host = dc_replace(seg, lazy_put=None)
    assert host.pack_memo == {}
    a = jit_exec.seg_flatten(host, frozenset(), frozenset())
    b = jit_exec.seg_flatten(host, frozenset(), frozenset())
    assert a is not b and all(x is y for x, y in zip(a, b))
    assert not any(k[0] == "flat" for k in host.pack_memo)
    key = jit_exec.layout_key(host)
    assert key == seg.pack_memo["layout"]
    assert key is not seg.pack_memo["layout"]


def test_racing_first_callers_build_the_key_once(node, index):
    """More threads than cores ask a segment with an empty memo for its
    key at once, with the interpreter switching threads often: one walk,
    one miss, and every caller gets that walk's tuple."""
    node.search(index, FVF)
    seg = dc_replace(_reader(node, index).segments[2])
    n = (os.cpu_count() or 1) + 4
    start = threading.Barrier(n)
    keys: list = []

    def ask() -> None:
        start.wait(timeout=30)
        keys.append(jit_exec.layout_key(seg))
    _hits0, misses0 = _memo_counts()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(keys) == n
    assert _memo_counts()[1] - misses0 == 1
    assert all(k is seg.pack_memo["layout"] for k in keys)


def test_the_operations_answer_as_the_reference(node, index, corpus):
    """The three operations' first answers (keys walked) and later
    answers (keys reused) are the reference's, within the cell's
    limits."""
    pool = gn.query_pool(corpus, {"pool": 9, "ops": {op: 1
                                                     for op in gn.OPS}},
                         np.random.default_rng([11, 13]),
                         np.random.default_rng(1))
    ref = gn.Reference(corpus, pool)
    hits0, _ = _memo_counts()
    for q in pool:
        ids, scores, total, sources = gn.parse_reply(
            node.search(index, gn.request_body(q, 10)))
        got = gn.compare(ref.scores(q), REQUEST, ids, scores, total,
                         sources)
        assert all(got.get(n, 0) <= LIMITS[n] for n in LIMITS), (q, got)
    assert _memo_counts()[0] - hits0 == (len(pool) - 1) * 3
