"""A whole run of the knn cell at the rehearsal size on the CPU: ``correct``
on the sound program, and false with each fault this path can have driven
through the run — an id changed in the drained ``top_docs``, two hits
swapped in the merge, a bfloat16 product."""

import numpy as np

from benchmarks import harness

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "dense768-knn.search-k10-c16"


def run(seed, seconds=3.0, trace=False):
    cell = harness.Cell(CELL, rehearsal=True)
    return harness.run_cell(cell, seed, seconds, trace, DEV)


def test_sound_run_is_correct():
    line = run(seed=2**31 + 78, trace=True)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 32
    assert list(line)[-1] == "compared"
    for ent in line["compared"].values():
        assert ent["value"] <= ent["limit"]
    assert set(line["observed"]) == {"ties_not_by_id", "hits_total"}
    assert line["observed"]["hits_total"] == 8192
    assert line["resident_bytes"] >= 8192 * 768 * 4
    m = line["metrics"]
    assert m["sched_batch_fill.tput"]["value"] >= 1.0
    # the CPU backend writes no device plane: no roofline, never a 0
    assert "knn_roofline" not in m and "lane_device_ms.tput" not in m
    assert "bm25_roofline" not in m
    line = run(seed=4100002999)
    assert line["correct"] is True and line["metrics"]["qps"]["value"] > 0


def test_an_id_changed_in_the_drained_top_docs(monkeypatch):
    from elasticsearch_tpu.search import phase
    real = phase.ShardSearcher._unpack_arm

    def altered(self, handle, host):
        if handle[0] == "knn":
            docs = host["top_docs"].copy()
            docs[:, 0] = np.where(docs[:, 0] >= 0, (docs[:, 0] + 4097)
                                  % 8192, docs[:, 0])
            host = {**host, "top_docs": docs}
        return real(self, handle, host)
    monkeypatch.setattr(phase.ShardSearcher, "_unpack_arm", altered)
    line = run(seed=9)
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > c["score_gap"]["limit"]
    assert c["rank_gap"]["value"] > c["rank_gap"]["limit"]


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
    line = run(seed=12)
    assert line["correct"] is False
    c = line["compared"]
    assert c["order_wrong"]["value"] > 0 and c["rank_gap"]["value"] == 0
    assert c["hits_wrong"]["value"] == 0


def test_a_bfloat16_product(monkeypatch):
    """What the parent's program does on a TPU (XLA's default precision
    for a float32 matmul), put into the knn lane here."""
    import jax.numpy as jnp
    from elasticsearch_tpu.ops import vector as vector_ops

    def one_pass(vecs, exists, qn):
        s = jnp.dot(qn.astype(jnp.bfloat16), vecs.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
        return jnp.where(exists[None, :], s, 0.0)
    from elasticsearch_tpu.search import jit_exec
    monkeypatch.setattr(vector_ops, "unit_scores_batch", one_pass)
    # a program of these shapes that an earlier run of this process
    # traced would be found in the program cache, sound as it was
    jit_exec.clear_cache()
    try:
        line = run(seed=10)
    finally:
        jit_exec.clear_cache()
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > 10 * c["score_gap"]["limit"]
    assert c["hits_wrong"]["value"] == 0
