"""The coordinator's merge (``controller.merge_responses``): score-ordered
shard results are merged by one array sort, field-sorted ones by the
comparator — and both give, element by element, what the comparator merge
gave for every request: the same page in the same order, the same
``_score``s, the same ``fetch_phase`` calls (shard, positions, order), and
the same totals, ``max_score`` and flags.

The oracle below is the merge as it was before the array sort: every hit a
``sort_docs`` ref, grouped in a dict by shard, reassembled through a
``(shard, position)`` dict.
"""

import numpy as np
import pytest

from elasticsearch_tpu.search import jit_exec
from elasticsearch_tpu.search.controller import merge_responses, sort_docs
from elasticsearch_tpu.search.phase import (ShardQueryResult,
                                            parse_search_request)


class Searcher:
    """A shard's fetch side: records every call, renders (shard, position,
    score) hits the way ``fetch_phase`` renders ``_score``."""

    def __init__(self, si, calls):
        self.si, self.calls = si, calls

    def fetch_phase(self, req, result, index_name, positions):
        self.calls.append((self.si, index_name, list(positions)))
        return [{"_index": index_name, "shard": self.si, "pos": p,
                 "_score": float(result.scores[p])
                 if result.sort_values is None else None}
                for p in positions]


def comparator_merge(names, req, results, searchers):
    """The merge before the array sort, for the hits and flags."""
    page = sort_docs(results, req)
    by_shard: dict = {}
    for ref in page:
        by_shard.setdefault(ref.shard_idx, []).append(ref.position)
    fetched = {}
    for si, positions in by_shard.items():
        hits = searchers[si].fetch_phase(req, results[si], names[si],
                                         positions)
        for pos, hit in zip(positions, hits):
            fetched[(si, pos)] = hit
    max_scores = [r.max_score for r in results if r.max_score is not None]
    return {"hits": [fetched[(ref.shard_idx, ref.position)] for ref in page],
            "total": sum(r.total for r in results),
            "max_score": max(max_scores) if max_scores and req.size > 0
            and not req.sort else None,
            "timed_out": any(r.timed_out for r in results),
            "terminated_early": any(r.terminated_early for r in results)}


def shard_results(seed, lens, levels=6, winner=None, sort=False,
                  terminate_after=None):
    """Per-shard top hits, best first, scores drawn from ``levels``
    float32 values so that ties within and across shards abound;
    ``winner``: one shard whose every hit outscores all others'."""
    rng = np.random.default_rng(seed)
    values = np.sort(rng.random(levels).astype(np.float32))[::-1]
    out = []
    for si, n in enumerate(lens):
        scores = np.sort(rng.choice(values, n))[::-1].astype(np.float32)
        if winner is not None and si == winner:
            scores = scores + np.float32(10.0)
        total = n + int(rng.integers(0, 50))
        r = ShardQueryResult(
            si, total if terminate_after is None
            else min(total, terminate_after),
            float(scores.max()) if n else None,
            rng.permutation(10_000)[:n].astype(np.int32), scores,
            [[int(v)] for v in rng.integers(0, 4, n)] if sort else None,
            {}, None)
        if terminate_after is not None and total >= terminate_after:
            r.terminated_early = True
        out.append(r)
    return out


CASES = {
    # name: (lens, request body, shard_results keywords)
    "ties-4x50": ([50, 50, 50, 50], {"size": 60}, {}),
    "ties-8x1000-two-levels": ([1000] * 8, {"size": 1000}, {"levels": 2}),
    "ties-one-level": ([30, 30, 30], {"size": 100}, {"levels": 1}),
    "empty-shards": ([0, 30, 0, 20], {"size": 40}, {}),
    "all-empty": ([0, 0, 0, 0], {"size": 10}, {}),
    "no-shards": ([], {"size": 10}, {}),
    "one-shard-all-winners": ([40, 40, 40, 40], {"size": 40},
                              {"winner": 2}),
    "page-from-size": ([50, 50, 50, 50], {"from": 13, "size": 25}, {}),
    "size-0": ([50, 50, 50, 50], {"size": 0}, {}),
    "from-past-the-end": ([20, 20], {"from": 500, "size": 10}, {}),
    "page-straddles-the-end": ([20, 20], {"from": 35, "size": 10}, {}),
    "terminate-after": ([30, 30, 30, 30], {"size": 50,
                                           "terminate_after": 40},
                        {"terminate_after": 40}),
    "field-sorted": ([30, 30, 30, 30],
                     {"size": 50, "sort": [{"n": {"order": "desc"}}]},
                     {"sort": True}),
}


@pytest.mark.parametrize("seed", [3, 17])
@pytest.mark.parametrize("case", sorted(CASES))
def test_array_merge_equals_the_comparator_merge(case, seed):
    lens, body, kw = CASES[case]
    req = parse_search_request(body)
    results = shard_results(seed, lens, **kw)
    names = [f"idx-{si % 2}" for si in range(len(lens))]
    got_calls, want_calls = [], []
    want = comparator_merge(
        names, req, results, [Searcher(si, want_calls)
                              for si in range(len(lens))])
    before = jit_exec.cache_stats()
    resp = merge_responses(names, req, results,
                           [Searcher(si, got_calls)
                            for si in range(len(lens))], 1.0, None)
    after = jit_exec.cache_stats()
    assert resp["hits"]["hits"] == want["hits"]
    assert [h["_score"] for h in resp["hits"]["hits"]] == \
        [h["_score"] for h in want["hits"]]
    assert got_calls == want_calls
    assert resp["hits"]["total"] == want["total"]
    assert resp["hits"]["max_score"] == want["max_score"]
    assert resp["timed_out"] == want["timed_out"]
    assert resp.get("terminated_early", False) == want["terminated_early"]
    assert resp["_shards"]["total"] == len(lens)
    sorted_by_field = "sort" in body
    assert after["merge_items_array"] - before["merge_items_array"] == \
        (0 if sorted_by_field else 1)
    assert after["merge_items_comparator"] - \
        before["merge_items_comparator"] == (1 if sorted_by_field else 0)


def test_the_page_is_best_first_and_ties_keep_shard_then_position():
    """The order itself, by hand: scores descending; among equal scores
    the lower shard index first, then the lower position."""
    req = parse_search_request({"size": 10})
    scores = [np.asarray(s, np.float32) for s in
              ([2.0, 1.0, 1.0], [3.0, 1.0], [], [2.0, 2.0, 0.5])]
    results = [ShardQueryResult(si, len(s), None,
                                np.arange(len(s), dtype=np.int32), s, None,
                                {}, None) for si, s in enumerate(scores)]
    calls = []
    resp = merge_responses("i", req, results,
                           [Searcher(si, calls) for si in range(4)], 0.0,
                           None)
    assert [(h["shard"], h["pos"]) for h in resp["hits"]["hits"]] == [
        (1, 0), (0, 0), (3, 0), (3, 1), (0, 1), (0, 2), (1, 1), (3, 2)]
    # one fetch a shard, in the order the page first meets it
    assert calls == [(1, "i", [0, 1]), (0, "i", [0, 1, 2]),
                     (3, "i", [0, 1, 2])]
