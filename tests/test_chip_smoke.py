"""chip_smoke.py off the chip: a CPU rehearsal walks every phase and can
never look like a pass, a run without a TPU stops before it loads data,
and the compile-cache helper leaves an operator's directory alone."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from elasticsearch_tpu.common import device  # noqa: E402


def _run(*argv, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)           # one CPU device, like one chip
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(REPO))


def test_rehearsal_walks_every_phase_and_never_passes():
    out = _run("--rehearse-cpu", "--docs", "6000")
    assert out.returncode == 3, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": False, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert '"ok": true' not in out.stdout
    text = out.stdout
    for phase in ("start-up", "write path", "load: bulk columnar ingest",
                  "first request", "warm request", "_msearch of 64",
                  "knn (first)", "impact-pruned (first)",
                  "impact-rescore (first)", "percolate (first)"):
        assert f"phase {phase}" in text, phase
    assert "counters: fallbacks=0 watchdog_stalls=0" in text
    assert "all agree with the oracle" in text
    assert "all phases passed" in text


def test_without_a_tpu_it_stops_before_loading_data():
    out = _run(timeout=120)
    assert out.returncode not in (0, 3)
    assert out.stdout.strip() == ""      # no phase ran, no result line
    assert "no TPU" in out.stderr


def test_docs_shrink_only_in_a_rehearsal():
    out = _run("--docs", "6000", timeout=120)
    assert out.returncode == 2 and out.stdout.strip() == ""


def test_corpus_and_queries_are_the_arrays_the_old_rig_made():
    """``make_corpus`` / ``make_queries`` came from the old rig's script
    when it was deleted: for a recorded seed they return, byte for byte,
    what they returned there (SHA-256 taken on the parent commit), so the
    smoke's oracle comparisons and ``tests/test_tpu_aot_compile.py``'s
    shapes do not drift."""
    rng = np.random.default_rng(7)
    corpus = chip_smoke.make_corpus(rng, 4096, 2000, chip_smoke.MEAN_LEN,
                                    chip_smoke.MAX_UNIQUE)
    queries = chip_smoke.make_queries(rng, 16, chip_smoke.QUERY_TERMS,
                                      corpus[3])
    got = {name: (str(a.dtype), a.shape, hashlib.sha256(
        np.ascontiguousarray(a).tobytes()).hexdigest())
        for name, a in zip(("uterms", "utf", "lens", "df", "toks",
                            "queries"), (*corpus, queries))}
    assert got == {
        "uterms": ("int32", (4096, 29), "cb99d6d5bbee3de21f2430482bdc54a3"
                   "4337363be0f2d451115ba7e5159f93fa"),
        "utf": ("float32", (4096, 29), "3d5992918ce18e67fb52e5db8bcf511f"
                "cbb1b7420ca69745b9480d385484aa12"),
        "lens": ("int32", (4096,), "cf8821b4944a0ec2d94e84639ff3044f"
                 "fd9c83083c22f767dfea7e0fe165cb80"),
        "df": ("int64", (2000,), "bfa6d7cc8039afdd76940bb7a75d6d42"
               "24e2fb3e98d182bf1999992eb281242d"),
        "toks": ("int32", (4096, 86), "0a81ed3bf845b1876240726554f14a40"
                 "9ee12e76fa1a362b31e04a75964be9a1"),
        "queries": ("int32", (16, 4), "5fce2f7db9efef205dbd33e8e77f9a37"
                    "7530809db7f29754496886445d5b9818"),
    }


@pytest.mark.parametrize("count", [1, 4])
def test_result_line_shape(count):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": count}
    assert chip_smoke.result_line(True, dev) == (
        '{"ok": true, "device": {"platform": "tpu", '
        f'"kind": "TPU v5 lite", "count": {count}}}}}')
    # ok is reserved for a TPU: never for a rehearsal, never for a CPU
    assert json.loads(chip_smoke.result_line(
        True, dev, rehearsal=True))["ok"] is False
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(chip_smoke.result_line(True, cpu))["ok"] is False
    assert json.loads(chip_smoke.result_line(False, dev))["ok"] is False


def test_compile_cache_helper(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set → the helper configures nothing;
    unset → the same fixed path inside the checkout on every call."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = device.ensure_compile_cache()
        assert first == device.ensure_compile_cache() == \
            str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
