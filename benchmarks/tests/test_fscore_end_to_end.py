"""A whole run of the function_score cell at the rehearsal size on the CPU:
``correct`` on the sound program, and false with each fault this path can
have driven through the run — a function dropped, latitude and longitude
swapped, two hits swapped in the merge, a stored source read from the
wrong row."""

from benchmarks import harness

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "geonames-fscore.fscore-mix-top10-c16"


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
    assert line["failed"] == 0 and line["attempted"] > 48
    assert list(line)[-1] == "compared"
    for ent in line["compared"].values():
        assert ent["value"] <= ent["limit"]
    m = line["metrics"]
    assert m["fscore_serial_share.tput"]["value"] == 0.0
    assert m["sched_batch_fill.tput"]["value"] >= 1.0
    # the CPU backend writes no device plane: no roofline, never a 0
    assert "fscore_roofline" not in m and "lane_device_ms.tput" not in m
    line = run(seed=4100004099)
    assert line["correct"] is True and line["metrics"]["qps"]["value"] > 0


def test_a_function_dropped(monkeypatch):
    """Every function scores 1: the query score alone is left."""
    import jax.numpy as jnp
    from elasticsearch_tpu.search import execute
    monkeypatch.setattr(
        execute.SegmentResolver, "_function_factor",
        lambda self, fn: lambda em, s: jnp.ones(em.n, jnp.float32))
    line = _fresh_programs(lambda: run(seed=31))
    assert line["correct"] is False
    c = line["compared"]
    assert c["score_gap"]["value"] > c["score_gap"]["limit"]
    assert c["total_wrong"]["value"] == 0


def test_latitude_and_longitude_swapped(monkeypatch):
    from elasticsearch_tpu.index import device_reader
    real = device_reader.DeviceGeoField

    def swapped(lat, lon, exists, column):
        return real(lat=lon, lon=lat, exists=exists, column=column)
    monkeypatch.setattr(device_reader, "DeviceGeoField", swapped)
    line = _fresh_programs(lambda: run(seed=32))
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
    line = run(seed=33)
    assert line["correct"] is False
    c = line["compared"]
    assert c["order_wrong"]["value"] > 0 and c["hits_wrong"]["value"] == 0


def test_a_source_read_from_the_wrong_row(monkeypatch):
    """The fetch returns the next row's stored document."""
    from elasticsearch_tpu.index import segment
    real = segment.PackedSources.__getitem__
    monkeypatch.setattr(segment.PackedSources, "__getitem__",
                        lambda self, i: real(self, (i + 1) % len(self)))
    line = run(seed=34)
    assert line["correct"] is False
    c = line["compared"]
    assert c["source_wrong"]["value"] > 0 and c["score_gap"]["value"] \
        <= c["score_gap"]["limit"]
