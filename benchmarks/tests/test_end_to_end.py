"""A whole run at the rehearsal size on the CPU, with the harness's look
for a chip skipped: ``correct`` is true on the sound program and false
with the timed path broken underneath, or with the books showing that the
chip path did not do the work."""

import copy
from pathlib import Path

import numpy as np
import pytest

from benchmarks import harness

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}
# a cell of the self-checks' own beside the committed one: an open loop of
# single searches through the same generator, readers and comparison
CELLS = Path(__file__).parent / "data" / "cells"


def run(cell_name, seed, seconds=3.0, trace=False):
    own = {"bench_file": CELLS / "BENCHMARK.json", "data_root": CELLS} \
        if "open" in cell_name else {}
    cell = harness.Cell(cell_name, rehearsal=True, **own)
    return harness.run_cell(cell, seed, seconds, trace, DEV)


@pytest.mark.parametrize("cell,metric", [
    ("msmarco-bm25.msearch64-top1000", "qps"),
    ("msmarco-bm25.search-top10-open", "p95_ms")])
def test_sound_run_is_correct(cell, metric):
    line = run(cell, seed=2**31 + 77)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert list(line)[-1] == "compared"
    for ent in line["compared"].values():
        assert ent["value"] <= ent["limit"]


def test_traced_run_reports_only_what_it_can_read():
    line = run("msmarco-bm25.search-top10-open", seed=5, trace=True)
    assert line["correct"] is True, line["compared"]
    m = line["metrics"]
    assert m["sched_batch_fill.lat"]["value"] >= 1.0
    assert m["gen_late_p95_ms"]["value"] >= 0.0
    # the CPU backend writes no device plane: no idle share, no roofline,
    # never a 0 in their place
    assert "device_idle_pct.lat" not in m and "lane_device_ms.lat" not in m
    assert "setup_s" not in m


def test_answer_altered_where_it_is_produced(monkeypatch):
    """The batched lane's result unpacked with one document id altered:
    the reply names a document the reference does not rank there."""
    from elasticsearch_tpu.ops import topk as topk_ops
    real = topk_ops.unpack_batch_result

    def altered(packed, k):
        scores, docs, counts = real(packed, k)
        docs = docs.copy()
        docs[:, 0] = np.where(docs[:, 0] >= 0, (docs[:, 0] + 4097) % 8192,
                              docs[:, 0])
        return scores, docs, counts
    monkeypatch.setattr(topk_ops, "unpack_batch_result", altered)
    line = run("msmarco-bm25.msearch64-top1000", seed=9)
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > c["score_gap"]["limit"] \
        or c["rank_gap"]["value"] > c["rank_gap"]["limit"] \
        or c["hits_wrong"]["value"] > 0


def test_hits_out_of_order(monkeypatch):
    """The right hits with the best two swapped where the reply's order
    is produced (the coordinator's merge): only the order is wrong, and
    that is enough."""
    from elasticsearch_tpu.action import search_action
    real = search_action.merge_shard_payloads

    def swapped(*args, **kwargs):
        out = real(*args, **kwargs)
        hits = out["hits"]["hits"]
        if len(hits) >= 2 and hits[0]["_score"] != hits[1]["_score"]:
            hits[0], hits[1] = hits[1], hits[0]
        return out
    monkeypatch.setattr(search_action, "merge_shard_payloads", swapped)
    line = run("msmarco-bm25.msearch64-top1000", seed=12)
    assert line["correct"] is False
    c = line["compared"]
    assert c["order_wrong"]["value"] > 0 and c["rank_gap"]["value"] == 0
    assert c["hits_wrong"]["value"] == 0 and "ties_not_by_id" not in c
    assert "ties_not_by_id" in line["observed"]


def test_total_altered(monkeypatch):
    from elasticsearch_tpu.ops import topk as topk_ops
    real = topk_ops.unpack_batch_result
    monkeypatch.setattr(
        topk_ops, "unpack_batch_result",
        lambda p, k: (lambda s, d, c: (s, d, c + (k >= 10)))(*real(p, k)))
    line = run("msmarco-bm25.msearch64-top1000", seed=10)
    assert line["correct"] is False
    assert line["compared"]["total_wrong"]["value"] > 0


BOOK = {"jit": {"fallbacks": 0, "misses": 3, "watchdog_stalls": 0,
                "watchdog_quarantines": 0, "fallback_reasons": {},
                "plane_breaker": {"state": "closed", "trips": 0,
                                  "errors_total": 0}},
        "lanes": {"reader-batch": {"dispatches": 5, "compiles": 3}},
        "scheduler": {"reconciled": True}}


def after(**jit):
    out = copy.deepcopy(BOOK)
    out["lanes"]["reader-batch"]["dispatches"] = 9
    out["jit"].update(jit)
    return out


@pytest.mark.parametrize("name,book", [
    ("eager_fallbacks", after(fallbacks=1)),
    ("bad_fallback_reasons", after(fallback_reasons={"device-error": 1})),
    ("breaker_trips", after(plane_breaker={"state": "open", "trips": 1,
                                           "errors_total": 1})),
    ("watchdog_stalls", after(watchdog_stalls=1)),
    ("compiles_in_window", after(misses=4)),
    ("lanes_missing", copy.deepcopy(BOOK)),
    ("scheduler_unreconciled",
     {**after(), "scheduler": {"reconciled": False}})])
def test_books_that_show_the_chip_path_did_not_do_the_work(name, book):
    checks = harness.book_checks(BOOK, book, ["reader-batch"])
    assert checks[name][0] > checks[name][1]
    clean = harness.book_checks(BOOK, after(), ["reader-batch"])
    assert all(v <= lim for v, lim in clean.values())
