"""A whole run of the phrase cell at the rehearsal size on the CPU:
``correct`` on the sound program, and false with each fault this path can
have driven through the run — the phrase clause dropped, a pad term that
matches nothing, two hits swapped in the merge."""

from benchmarks import harness

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "msmarco-phrase.bool-phrase-top10-c16"


def run(seed, seconds=3.0, trace=False):
    cell = harness.Cell(CELL, rehearsal=True)
    return harness.run_cell(cell, seed, seconds, trace, DEV)


def _fresh_programs(fn):
    """A program of these shapes that an earlier run of this process
    traced would be found in the program cache, sound as it was."""
    from elasticsearch_tpu.search import jit_exec
    jit_exec.clear_cache()
    try:
        return fn()
    finally:
        jit_exec.clear_cache()


def test_sound_run_is_correct():
    line = run(seed=2**31 + 36, trace=True)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 32
    assert list(line)[-1] == "compared"
    for ent in line["compared"].values():
        assert ent["value"] <= ent["limit"]
    assert line["observed"]["phrase_hits"] > 0
    m = line["metrics"]
    assert m["sched_batch_fill.tput"]["value"] >= 1.0
    assert 0.0 < m["phrase_pad_share.tput"]["value"] < 100.0
    # the CPU backend writes no device plane: no roofline, never a 0
    assert "phrase_roofline" not in m and "lane_device_ms.tput" not in m
    line = run(seed=4100003699)
    assert line["correct"] is True and line["metrics"]["qps"]["value"] > 0


def test_the_phrase_clause_dropped(monkeypatch):
    from elasticsearch_tpu.search import execute
    monkeypatch.setattr(execute.SegmentResolver, "_res_MatchPhraseQuery",
                        lambda self, query: self._zeros())
    line = _fresh_programs(lambda: run(seed=21))
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > c["score_gap"]["limit"]
    assert c["total_wrong"]["value"] == 0


def test_a_pad_term_that_matches_nothing(monkeypatch):
    import jax.numpy as jnp
    from elasticsearch_tpu.ops import phrase as phrase_ops
    real = phrase_ops._phrase_counts

    def pads_absent(tokens, qtids, deltas):
        return real(tokens, jnp.where(qtids == phrase_ops.PAD_TID, -1,
                                      qtids), deltas)
    monkeypatch.setattr(phrase_ops, "_phrase_counts", pads_absent)
    line = _fresh_programs(lambda: run(seed=22))
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > c["score_gap"]["limit"]


def test_two_hits_swapped_in_the_merge(monkeypatch):
    from elasticsearch_tpu.action import search_action
    real = search_action.merge_shard_payloads

    def swapped(*args, **kwargs):
        out = real(*args, **kwargs)
        hits = out["hits"]["hits"]
        if len(hits) >= 2 and hits[0]["_score"] != hits[1]["_score"]:
            hits[0], hits[1] = hits[1], hits[0]
        return out
    monkeypatch.setattr(search_action, "merge_shard_payloads", swapped)
    line = run(seed=23)
    assert line["correct"] is False
    c = line["compared"]
    assert c["order_wrong"]["value"] > 0 and c["hits_wrong"]["value"] == 0
