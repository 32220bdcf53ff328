"""The four-chip cell through a whole rehearsal run on four virtual CPU
devices: sound, it is ``correct`` with every item served by the plane in
one dispatch; with a hit moved to the wrong shard, or two hits swapped in
the merge, it is caught."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np
import pytest

from benchmarks import harness

CELL = "msmarco-bm25-4shard.msearch64-top1000-4chip"
DEV = {"platform": "cpu", "kind": "cpu", "count": 4}


@pytest.fixture(autouse=True)
def four_devices():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("the backend was started with fewer than four devices")


def run(seed, trace=False):
    cell = harness.Cell(CELL, rehearsal=True)
    return harness.run_cell(cell, seed, 3.0, trace, DEV)


def test_sound_run_is_correct_and_every_item_is_the_planes():
    from elasticsearch_tpu.search import jit_exec
    before = jit_exec.cache_stats()
    line = run(2 ** 31 + 91, trace=True)
    after = jit_exec.cache_stats()
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 64
    assert after["plane_items_fallback"] == before["plane_items_fallback"]
    served = after["plane_items_served"] - before["plane_items_served"]
    dispatches = after["plane_dispatches"] - before["plane_dispatches"]
    # the harness's own _count is a dispatch of one item
    assert served >= line["attempted"] and dispatches >= served // 64
    m = line["metrics"]
    assert m["plane_host_ms_per_query.tput"]["value"] > 0
    # the CPU backend writes no device plane: no roofline, never a 0
    assert "plane_roofline" not in m and "device_idle_pct.tput" not in m
    # a quarter of the resident bytes a device is the program's to show;
    # here: what is resident is the columns once (tokens filler + masks
    # within 5%)
    assert line["resident_bytes"] <= 1.05 * 8 * 2048 * (224 * 8 + 4)


def test_a_hit_moved_to_the_wrong_shard_is_caught(monkeypatch):
    """The plane's global id of every answer's best hit moved by one
    shard's stride where the program hands it to the split: the reply
    names a document of the next shard that the reference does not rank
    there."""
    from elasticsearch_tpu.parallel import mesh_engine
    real = mesh_engine.MeshEngineSearcher.search_batch

    def moved(self, bodies, global_stats=True):
        outs = real(self, bodies, global_stats=global_stats)
        for out in outs:
            if len(out["doc_ids"]) > 1:
                ids = np.array(out["doc_ids"])
                ids[0] = (ids[0] + self.shard_stride) % (
                    self.shard_stride * self.n_shards)
                out["doc_ids"] = ids
        return outs
    monkeypatch.setattr(mesh_engine.MeshEngineSearcher, "search_batch",
                        moved)
    line = run(17)
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > c["score_gap"]["limit"] \
        or c["rank_gap"]["value"] > c["rank_gap"]["limit"] \
        or c["hits_wrong"]["value"] > 0


def test_two_hits_swapped_in_the_merge_are_caught(monkeypatch):
    from elasticsearch_tpu.search import controller
    real = controller.merge_responses

    def swapped(*args, **kwargs):
        out = real(*args, **kwargs)
        hits = out["hits"]["hits"]
        if len(hits) >= 2 and hits[0]["_score"] != hits[1]["_score"]:
            hits[0], hits[1] = hits[1], hits[0]
        return out
    monkeypatch.setattr(controller, "merge_responses", swapped)
    line = run(19)
    assert line["correct"] is False
    c = line["compared"]
    assert c["order_wrong"]["value"] > 0 and c["rank_gap"]["value"] == 0
    assert c["hits_wrong"]["value"] == 0


def test_a_program_without_the_mesh_setting_fails_at_once(monkeypatch):
    from benchmarks.corpora import zipf_text_sharded as sharded
    from elasticsearch_tpu.node import Node
    monkeypatch.delattr(Node, "_install_serving_mesh")
    cell = harness.Cell(CELL, rehearsal=True)
    with pytest.raises(SystemExit, match="search.mesh"):
        sharded.generate(cell.config, 3)
