"""chip_smoke.py off the chip: a CPU rehearsal walks every phase and can
never look like a pass, a run without a TPU stops before it loads data,
and the compile-cache helper leaves an operator's directory alone."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
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
