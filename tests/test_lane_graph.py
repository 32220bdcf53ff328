"""Lane-admission graph (tier-1): the ``--emit-lane-graph`` artifact
round-trips against the LIVE runtime registries — vocabularies, decline
edges, counters and admission-predicate locations can never drift from
the code — plus the counter-registry ↔ ``_nodes/stats`` surface
round-trip and the CLI satellites (``--diff``, ``--emit-lane-graph``,
``--strict-suppressions``)."""

from __future__ import annotations

import ast
import json
import subprocess
import sys
import types
from pathlib import Path

import pytest

from elasticsearch_tpu.analysis.lint import (
    DEFAULT_CONFIG, lint_paths, parse_contexts)
from elasticsearch_tpu.analysis.lint.cli import main as lint_main
from elasticsearch_tpu.analysis.lint.lane_graph import (
    build_lane_graph, render_lane_graph)
from elasticsearch_tpu.analysis.lint.program import ProgramIndex
from elasticsearch_tpu.search import lanes

REPO = Path(__file__).resolve().parents[1]
ARTIFACT = REPO / "elasticsearch_tpu" / "analysis" / "lane_graph.json"


@pytest.fixture(scope="module")
def graph():
    contexts, errors = parse_contexts([str(REPO / "elasticsearch_tpu")])
    assert errors == []
    program = ProgramIndex(contexts, DEFAULT_CONFIG)
    return build_lane_graph(program, DEFAULT_CONFIG)


# ---------------------------------------------------------------------------
# registry ↔ graph round-trip
# ---------------------------------------------------------------------------

def test_graph_reasons_match_runtime_registry(graph):
    assert set(graph["lanes"]) == set(lanes.LANE_REASONS)
    for lane, spec in graph["lanes"].items():
        assert tuple(spec["reasons"]) == lanes.LANE_REASONS[lane]


def test_graph_edges_match_runtime_registry(graph):
    got = [(e["from"], e["to"], e["reason"])
           for e in graph["decline_edges"]]
    assert got == list(lanes.DECLINE_EDGES)
    for e in graph["decline_edges"]:
        # an edge's reason is part of the declining lane's vocabulary
        # and has at least one real decline site on the tree
        assert e["reason"] in lanes.LANE_REASONS[e["from"]]
        assert e["sites"], e


def test_graph_counters_match_runtime_registry(graph):
    assert graph["counters"]["JIT_COUNTERS"] == \
        sorted(lanes.JIT_COUNTERS)
    assert graph["counters"]["DATA_LAYER_COUNTERS"] == \
        sorted(lanes.DATA_LAYER_COUNTERS)
    assert graph["counters"]["PERCOLATE_COUNTERS"] == \
        sorted(lanes.PERCOLATE_COUNTERS)
    # the cost observatory's gauge registry + program-lane vocabulary
    # ride the same artifact (the planner's observable cost surface)
    assert graph["counters"]["PROGRAM_COST"] == \
        sorted(lanes.PROGRAM_COST)
    assert graph["program_lanes"] == sorted(lanes.PROGRAM_LANES)


def test_graph_admissions_resolve_to_live_defs(graph):
    """LANE_ADMISSIONS names survive refactors only if this keeps
    passing: every admission location points at a real ``def`` of that
    function, and every reason has at least one decline site."""
    for lane, spec in graph["lanes"].items():
        adm = spec["admission"]
        assert adm is not None, f"{lane}: admission spec unresolved"
        src = (REPO / adm["path"]).read_text(encoding="utf-8")
        line = src.splitlines()[adm["line"] - 1]
        fn_name = adm["function"].rsplit(".", 1)[-1]
        assert f"def {fn_name}" in line, (lane, adm, line)
        for reason, sites in spec["reasons"].items():
            assert sites, f"{lane}/{reason}: no decline site found"
            for s in sites:
                assert (REPO / s["path"]).exists()


def test_committed_artifact_is_fresh(graph):
    """The checked-in analysis/lane_graph.json is byte-identical to a
    fresh emit — scripts/lint_gate.sh regenerates it; a stale commit
    fails here."""
    assert ARTIFACT.exists(), "run: estpu-lint --emit-lane-graph"
    assert ARTIFACT.read_text(encoding="utf-8") == \
        render_lane_graph(graph)


# ---------------------------------------------------------------------------
# counter registry ↔ stats-surface round-trip (runtime)
# ---------------------------------------------------------------------------

def test_nodes_stats_surfaces_every_registered_counter(tmp_path):
    """_nodes/stats output keys ⊇ registered counters: the jit section
    carries every JIT_COUNTERS key and its data_layer every
    DATA_LAYER_COUNTERS key, so a registered counter can never be
    silently absent from the observable surface."""
    from elasticsearch_tpu.node import Node
    n = Node({}, data_path=tmp_path / "n").start()
    try:
        stats = n.local_node_stats()
        jit = stats["indices"]["jit"]
        missing = set(lanes.JIT_COUNTERS) - set(jit)
        assert not missing, missing
        assert set(jit["data_layer"]) == set(lanes.DATA_LAYER_COUNTERS)
        assert "percolate_fallback_reasons" in jit
        # the node_local attributed slice mirrors the same key set
        assert set(lanes.JIT_COUNTERS) <= set(jit["node_local"])
    finally:
        n.close()


def test_percolator_stats_built_from_registry():
    from elasticsearch_tpu.search.percolator import PercolatorRegistry
    meta = types.SimpleNamespace(name="fix", uuid="u1", settings={})
    reg = PercolatorRegistry(meta)
    assert set(reg.stats) == set(lanes.PERCOLATE_COUNTERS)
    assert reg.stats["builds"] == 1       # counted at construction


def test_unregistered_reason_is_rejected_at_runtime():
    from elasticsearch_tpu.search import jit_exec
    with pytest.raises(AssertionError):
        jit_exec.note_knn_fallback("not-a-registered-reason")
    jit_exec.note_knn_fallback("mixed-shapes")   # registered: fine


# ---------------------------------------------------------------------------
# a program lane cannot outlive its last caller
# ---------------------------------------------------------------------------

_COMPILE_SEAMS = {"_get_compiled": 2, "observed_compile": 0}


def _callee(call: ast.Call):
    fn = call.func
    return fn.id if isinstance(fn, ast.Name) else \
        fn.attr if isinstance(fn, ast.Attribute) else None


def _lane_literal(call: ast.Call):
    """The lane a compile site names: the ``lane=`` keyword or the
    seam's positional lane argument, as a string literal (a forwarded
    parameter, as inside ``_get_compiled`` itself, names no lane)."""
    pos = _COMPILE_SEAMS[_callee(call)]
    args = [kw.value for kw in call.keywords if kw.arg == "lane"]
    if not args and len(call.args) > pos:
        args = [call.args[pos]]
    for a in args:
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value
    return None


@pytest.fixture(scope="module")
def lane_builders():
    """→ ({lane: {(path, outermost def name, first line, last line)}},
    {name: [(path, line)]} for every name or attribute the package's
    code reads — a call, or a function handed on as a value)."""
    builders: dict = {}
    uses: dict = {}
    for path in sorted((REPO / "elasticsearch_tpu").rglob("*.py")):
        tree = ast.parse(path.read_text(encoding="utf-8"))
        tops = [n for n in tree.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for cls in (n for n in tree.body if isinstance(n, ast.ClassDef)):
            tops += [n for n in cls.body if isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for node in ast.walk(tree):
            if isinstance(node, (ast.Name, ast.Attribute)):
                name = node.id if isinstance(node, ast.Name) else node.attr
                uses.setdefault(name, []).append((path, node.lineno))
        for fn in tops:
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and \
                        _callee(node) in _COMPILE_SEAMS and \
                        (lane := _lane_literal(node)) is not None:
                    builders.setdefault(lane, set()).add(
                        (path, fn.name, fn.lineno, fn.end_lineno))
    return builders, uses


@pytest.mark.parametrize("lane", lanes.PROGRAM_LANES)
def test_every_program_lane_is_reached_from_the_package(lane, lane_builders):
    """Every registered program lane has a compile site, and a function
    that holds one is used (called, or handed on by name or attribute)
    in the package outside its own body: a program builder whose last
    caller went goes with it, and with its entry in ``PROGRAM_LANES``."""
    builders, uses = lane_builders
    assert builders.get(lane), f"no compile site names lane {lane!r}"
    reached = [
        (name, str(at.relative_to(REPO)), line)
        for path, name, lo, hi in builders[lane]
        for at, line in uses.get(name, ())
        if not (at == path and lo <= line <= hi)]
    assert reached, (
        f"lane {lane!r}: nothing in elasticsearch_tpu/ uses "
        f"{sorted(b[1] for b in builders[lane])}")


# ---------------------------------------------------------------------------
# CLI satellites
# ---------------------------------------------------------------------------

FIXDIR = Path(__file__).resolve().parent / "lint_fixtures"


def test_cli_emit_lane_graph(tmp_path, capsys):
    out = tmp_path / "graph.json"
    rc = lint_main([str(REPO / "elasticsearch_tpu" / "search" /
                        "lanes.py"), "--emit-lane-graph", str(out)])
    assert rc == 0
    capsys.readouterr()
    doc = json.loads(out.read_text(encoding="utf-8"))
    assert set(doc["lanes"]) == set(lanes.LANE_REASONS)


def test_cli_strict_suppressions(capsys):
    fixture = str(FIXDIR / "stale_allow.py")
    assert lint_main([fixture]) == 0          # warning tier: gate passes
    out = capsys.readouterr().out
    assert "allow-stale" in out and "warning" in out
    assert lint_main([fixture, "--strict-suppressions"]) == 1
    capsys.readouterr()


def test_cli_diff_filters_to_changed_files(tmp_path, monkeypatch, capsys):
    """--diff REF: the whole program is analyzed, but the report (and
    exit code) covers only files changed vs the ref."""
    repo = tmp_path / "r"
    repo.mkdir()
    clean = ("import threading\n_cache_lock = threading.Lock()\n"
             "_c = {}\n\ndef f():\n    with _cache_lock:\n"
             "        _c['k'] = 1\n")
    dirty = clean + "\n\ndef g():\n    _c['k'] = 2\n"
    (repo / "a.py").write_text(dirty)     # pre-existing violation
    (repo / "b.py").write_text(clean)
    env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
    for cmd in (["git", "init", "-q"], ["git", "add", "."],
                ["git", "-c", "user.name=t", "-c", "user.email=t@t",
                 "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=repo, check=True, env={
            **env, "PATH": "/usr/bin:/bin:/usr/local/bin"})
    monkeypatch.chdir(repo)
    # full run sees a.py's violation…
    assert lint_main(["a.py", "b.py"]) == 1
    capsys.readouterr()
    # …but nothing changed vs HEAD, so --diff reports clean
    assert lint_main(["a.py", "b.py", "--diff", "HEAD"]) == 0
    capsys.readouterr()
    # introduce a violation in b.py only: --diff flags exactly it
    (repo / "b.py").write_text(dirty)
    assert lint_main(["a.py", "b.py", "--diff", "HEAD", "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert {f["path"] for f in doc["findings"]
            if not f["suppressed"]} == {"b.py"}


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
