"""Shared AST plumbing for plane-lint.

One :class:`ModuleContext` per analyzed file: the parsed tree with parent
links, a function index (qualnames, lexical nesting, owning class), the
import-alias table (so ``jit_exec.device_fault_point`` resolves across
modules), and the inline-suppression index for the
``# estpu: allow[rule-id] <reason>`` syntax.

Suppressions attach to the STATEMENT they share a line with (any line of
a multi-line statement works) or to the line directly above it; a bare
``allow`` with no reason string does not suppress — it surfaces as an
``allow-missing-reason`` finding instead, so every surviving suppression
documents why the invariant does not apply.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from dataclasses import dataclass, field

_SUPPRESS_RE = re.compile(
    r"#\s*estpu:\s*allow\[([A-Za-z0-9_-]+)\]\s*(.*?)\s*$")

#: rule-id → family (the JSON report counts by family; the ids are what
#: suppressions name)
RULE_FAMILIES = {
    "breaker-unreleased": "breaker-discipline",
    "breaker-double-release": "breaker-discipline",
    "device-raw-call": "device-seam",
    "device-unguarded": "device-seam",
    "device-unknown-site": "device-seam",
    "recompile-request-path": "recompile-hazard",
    "recompile-unbucketed-key": "recompile-hazard",
    "lock-order": "lock-discipline",
    "lock-unguarded-state": "lock-discipline",
    "host-sync-hot-loop": "host-sync",
    "span-unscoped-site": "span-discipline",
    "span-unended": "span-discipline",
    # trace-purity: nothing reachable from inside a traced body may
    # touch host state (the PR 10 trace-time-import bug class)
    "trace-impure-import": "trace-purity",
    "trace-impure-global": "trace-purity",
    "trace-impure-state-write": "trace-purity",
    "trace-impure-call": "trace-purity",
    "trace-impure-capture": "trace-purity",
    # counter-discipline: every bump registered, every registered key
    # bumped, every store surfaced from the registry, every registry
    # reachable from the /_prometheus exposition
    "counter-unregistered": "counter-discipline",
    "counter-unbumped": "counter-discipline",
    "counter-unsurfaced": "counter-discipline",
    "counter-unexported": "counter-discipline",
    # fallback-taxonomy: one closed reason vocabulary per lane
    "fallback-unknown-reason": "fallback-taxonomy",
    "fallback-duplicate-reason": "fallback-taxonomy",
    "fallback-unused-reason": "fallback-taxonomy",
    "fallback-unresolved-reason": "fallback-taxonomy",
    # program-cost-discipline: every program compile flows through the
    # observed_compile seam (so the cost observatory sees it), under a
    # registered program-lane literal
    "program-cost-unobserved": "program-cost-discipline",
    "program-cost-unknown-lane": "program-cost-discipline",
    # unbounded-wait: every blocking wait on the serving path carries a
    # timeout (a wedged dispatch must become a typed failover, never a
    # hung request — the stall-tolerance ladder's static half)
    "unbounded-wait": "unbounded-wait",
    # plan-node-spans: every planner-emitted plan node opens a literal
    # ``plan.*`` span and carries a registered planner fallback reason
    # (the cost-driven planner's observability contract)
    "plan-node-unspanned": "plan-node-spans",
    "plan-node-unregistered-reason": "plan-node-spans",
    "allow-missing-reason": "meta",
    "allow-stale": "meta",
}


@dataclass
class Finding:
    rule: str
    path: str
    line: int
    message: str
    suppressed: bool = False
    suppress_reason: str | None = None
    #: warning-tier findings (the stale-suppression audit) are reported
    #: but do not fail the gate unless --strict-suppressions promotes
    #: them
    warning: bool = False

    @property
    def family(self) -> str:
        return RULE_FAMILIES.get(self.rule, "unknown")

    def to_dict(self) -> dict:
        return {"rule": self.rule, "family": self.family,
                "path": self.path, "line": self.line,
                "message": self.message, "suppressed": self.suppressed,
                "suppress_reason": self.suppress_reason,
                "warning": self.warning}

    def render(self) -> str:
        tag = "allowed" if self.suppressed else \
            ("warning" if self.warning else "error")
        out = (f"{self.path}:{self.line}: [{self.rule}] {tag}: "
               f"{self.message}")
        if self.suppressed and self.suppress_reason:
            out += f" (reason: {self.suppress_reason})"
        return out


@dataclass
class LintConfig:
    """Everything repo-specific the rules key on — overridable so the
    fixture suite can point the seam/hot-path scoping at synthetic
    files."""

    #: modules allowed to touch the device directly (fnmatch over the
    #: posix relpath) — the seam allowlist from the device-seam rule
    seam_modules: tuple = ("*/search/jit_exec.py",
                           "*/parallel/mesh_engine.py",
                           "*/parallel/mesh.py",
                           "*/ops/*.py")
    #: modules whose dispatch loops the host-sync rule polices
    hot_modules: tuple = ("*/search/jit_exec.py",
                          "*/parallel/mesh_engine.py",
                          "*/search/percolator.py",
                          "*/ops/percolate.py")
    #: the site classes device_fault_point may name
    #: (testing_disruption.DEVICE_FAULT_SITES + READER_UPLOAD_SITE;
    #: impact-upload / blockmax-compose / pruning-dispatch are the
    #: impact-ordered lane's device touchpoints)
    known_sites: tuple = ("dispatch", "compile", "upload", "compose",
                          "plane-dispatch", "percolate", "reader-upload",
                          "impact-upload", "blockmax-compose",
                          "pruning-dispatch",
                          # dense/late-interaction lane: vector block
                          # upload, fused MaxSim + hybrid-fusion
                          # dispatches
                          "vector-upload", "maxsim-dispatch",
                          "fusion-dispatch",
                          # the planner's composed impact→rescore arm
                          "rescore-dispatch",
                          # mesh-sharded retrieval lanes: placed block
                          # upload, pod-slice impact sweep dispatch,
                          # cross-chip knn candidate merge dispatch
                          "block-placement-upload",
                          "impact-shard-dispatch", "knn-mesh-merge")
    #: site classes that mark a LOOP as a dispatch loop (host-sync rule)
    dispatch_sites: tuple = ("dispatch", "plane-dispatch", "percolate",
                             "pruning-dispatch", "maxsim-dispatch",
                             "fusion-dispatch", "rescore-dispatch",
                             "impact-shard-dispatch", "knn-mesh-merge")
    #: site classes that dominate a raw ``jax.device_put`` inside a seam
    #: module (the upload/compose family of device touchpoints)
    upload_sites: tuple = ("upload", "compose", "reader-upload",
                           "impact-upload", "blockmax-compose",
                           "vector-upload", "block-placement-upload")
    #: the seam entry points (calls routed through these are guarded)
    fault_point_names: tuple = ("device_fault_point",)
    seam_wrappers: tuple = ("seam_device_put", "seam_jit")
    #: span constructors the span-discipline rule pairs with fault
    #: points (and requires to be used as `with` contexts)
    span_fns: tuple = ("device_span",)
    #: modules exempt from span-discipline (the tracer's own home —
    #: constructors are DEFINED there, not leaked)
    span_exempt_modules: tuple = ("*/observability/*",)
    #: closures passed (by name) to these functions are compiled behind
    #: a guarded, cache-keyed trampoline (observed_compile owns the
    #: fault point + cost-table stamp for the lowered program it
    #: receives)
    trampolines: tuple = ("_get_compiled", "observed_compile")
    #: referencing any of these inside a function counts as consulting
    #: the PROGRAM-layer cache (recompile rule)
    cache_markers: tuple = ("_get_compiled", "_program_cache",
                            "note_mesh_program")
    #: calls that construct a compiled program (recompile rule tracks
    #: raw jax.jit plus the repo's guarded wrapper)
    jit_constructors: tuple = ("jax.jit", "seam_jit")
    #: batch-size bucketing helpers (recompile key rule)
    bucket_fns: tuple = ("pow2_bucket",)
    #: charge constructors the breaker rule pairs with .release()
    charge_classes: tuple = ("OneShotCharge",)
    #: methods whose callers are asserted (by name) to hold the lock
    locked_suffix: str = "_locked"
    #: container methods that mutate in place (lock-discipline rule)
    mutators: tuple = ("append", "add", "update", "clear", "pop",
                       "popitem", "setdefault", "extend", "remove",
                       "discard", "move_to_end", "insert")

    # ---- trace-purity (whole-program) ------------------------------------
    #: callables whose function argument executes at TRACE time, matched
    #: by last name (``seam_jit(fn)``, ``jax.vmap(fn)``, ``@jax.jit``)
    trace_stagers: tuple = ("jit", "vmap", "pmap", "seam_jit",
                            "shard_map", "shard_map_compat")
    #: …and by dotted suffix, for names too generic to match bare
    #: (``lax.map`` must not swallow the builtin ``map``)
    trace_stagers_dotted: tuple = ("lax.scan", "lax.map", "lax.cond",
                                   "lax.while_loop", "lax.fori_loop",
                                   "lax.switch", "jax.checkpoint",
                                   "jax.remat")
    #: fnmatch patterns over a callee's dotted name: calling one of
    #: these from trace-reachable code is a side effect (counter bumps,
    #: logging, IO — they run at TRACE time, once per compile, not per
    #: request; under concurrency, with foreign tracers in scope)
    trace_side_effects: tuple = ("print", "open", "input", "note_*",
                                 "_bump", "logging.*", "*.warning",
                                 "*.info", "*.debug", "*.error")

    # ---- counter-discipline (whole-program) ------------------------------
    #: modules whose counter stores the rule polices
    counter_modules: tuple = ("*/search/jit_exec.py",
                              "*/parallel/mesh_engine.py",
                              "*/search/percolator.py")
    #: the registry module (parsed for the declared key sets)
    counter_registry_modules: tuple = ("*/search/lanes.py",)
    #: names of the registry dicts inside the registry module
    counter_registry_names: tuple = ("JIT_COUNTERS",
                                     "DATA_LAYER_COUNTERS",
                                     "PERCOLATE_COUNTERS")
    #: last name of a counter-store dict (``_stats[...] += n`` /
    #: ``self.stats[...] += n``) inside a counter module
    counter_stores: tuple = ("_stats", "_data_layer", "stats")
    #: functions whose first argument is a counter key
    counter_bump_fns: tuple = ("_bump",)
    #: the OpenMetrics exporter module(s): every registry dict must be
    #: REFERENCED there (the exposition iterates the registries, so a
    #: referenced registry exports every key by construction — and an
    #: unreferenced one is a whole counter family invisible to scrapes)
    exporter_modules: tuple = ("*/observability/openmetrics.py",)

    # ---- program-cost-discipline -----------------------------------------
    #: modules whose program compiles must flow through the
    #: observed_compile seam (the compiled-program homes)
    cost_seam_modules: tuple = ("*/search/jit_exec.py",
                                "*/parallel/mesh_engine.py")
    #: the seam functions allowed to call ``.compile()`` on a lowered
    #: program (everything else routes through them)
    cost_seam_fns: tuple = ("observed_compile",)
    #: callables whose ``lane`` argument must be a PROGRAM_LANES string
    #: literal at the call site (forwarded parameters inside these
    #: functions themselves are exempt, the seam-wrapper discipline)
    cost_lane_callers: tuple = ("observed_compile", "_get_compiled")
    #: the registered program lanes (mirrors lanes.PROGRAM_LANES; the
    #: tier-1 fixture suite asserts the two stay in sync)
    program_lanes: tuple = ("segment", "reader-batch", "streamed",
                            "percolate", "impact-eager", "impact-pruned",
                            "impact-rescore", "knn", "mesh", "impact-mesh",
                            "knn-mesh")
    #: gauge registries in the lane-registry module: emitted into
    #: lane_graph.json next to the counter registries and required (by
    #: counter-unexported) to be referenced by the exporter, but their
    #: keys are computed gauges — never bumped, so the unbumped check
    #: skips them
    gauge_registry_names: tuple = ("PROGRAM_COST",)

    # ---- unbounded-wait --------------------------------------------------
    #: modules where every blocking ``.result()``/``.join()``/``.get()``/
    #: ``.wait()`` must carry a timeout: the device executor, the
    #: dispatcher, the batch scheduler, and the coordinator fan-out —
    #: the layers a wedged device dispatch would otherwise hang.
    #: Worker-loop homes (threadpool, cluster service) stay out: a
    #: worker idling for its next task may block without bound.
    wait_modules: tuple = ("*/search/jit_exec.py",
                           "*/search/scheduler.py",
                           "*/search/watchdog.py",
                           "*/action/search_action.py")

    # ---- fallback-taxonomy (whole-program) -------------------------------
    #: reason-noting callables, by last name → lane whose vocabulary
    #: the literal reason must come from
    fallback_noters: tuple = (("note_plane_fallback", "plane"),
                              ("_note_plane_fallback", "plane"),
                              ("note_fallback", "plane"),
                              ("note_impact_fallback", "impact"),
                              ("note_knn_fallback", "knn"),
                              ("note_percolate_fallback", "percolate"),
                              ("note_scheduler_shed", "scheduler"),
                              ("note_planner_fallback", "planner"))
    #: the lane-registry module and its vocabulary / edge / admission
    #: dict names (the --emit-lane-graph source of truth)
    lane_registry_modules: tuple = ("*/search/lanes.py",)
    lane_reasons_name: str = "LANE_REASONS"
    lane_edges_name: str = "DECLINE_EDGES"
    lane_admissions_name: str = "LANE_ADMISSIONS"

    # ---- plan-node-spans (whole-program) ---------------------------------
    #: the planner module(s): every plan-node constructor call there
    #: must pass a literal ``plan.*`` span and a registered planner
    #: fallback reason
    planner_modules: tuple = ("*/search/planner.py",)
    #: plan-node constructor names the rule scans for
    plan_node_ctors: tuple = ("PlanNode",)
    #: required prefix of a plan node's span literal
    plan_span_prefix: str = "plan."
    #: the lane whose vocabulary plan-node ``fallback=`` literals must
    #: come from
    plan_reason_lane: str = "planner"


DEFAULT_CONFIG = LintConfig()


def module_matches(relpath: str, patterns: tuple) -> bool:
    rel = relpath.replace("\\", "/")
    return any(fnmatch.fnmatch(rel, pat) or fnmatch.fnmatch("*/" + rel, pat)
               for pat in patterns)


@dataclass
class FunctionInfo:
    node: object                       # FunctionDef | AsyncFunctionDef | Lambda
    name: str
    qualname: str
    parent: "FunctionInfo | None"
    class_name: str | None


@dataclass
class ModuleContext:
    relpath: str
    source: str
    tree: ast.Module = None
    suppressions: dict = field(default_factory=dict)   # line → [(rule, reason)]
    #: (comment line, rule) pairs a finding actually consumed — the
    #: complement is the stale-suppression audit's input
    used_suppressions: set = field(default_factory=set)
    functions: list = field(default_factory=list)
    _fn_of_node: dict = field(default_factory=dict)    # id(node) → FunctionInfo
    import_aliases: dict = field(default_factory=dict)  # alias → module path

    def __post_init__(self):
        self.tree = ast.parse(self.source)
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._pl_parent = node
        self._index_suppressions()
        self._index_functions()
        self._index_imports()

    # ---- suppressions -----------------------------------------------------

    def _index_suppressions(self) -> None:
        # tokenize so only REAL comments count — a docstring describing
        # the allow syntax must not suppress anything
        import io
        import tokenize
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _SUPPRESS_RE.search(tok.string)
                if m:
                    self.suppressions.setdefault(
                        tok.start[0], []).append((m.group(1), m.group(2)))
        except tokenize.TokenError:
            pass

    def suppression_for(self, rule: str, node) -> "tuple | None":
        """→ (reason,) if an allow[rule] comment covers `node` (any line
        of its statement, or the line directly above)."""
        stmt = self.enclosing_stmt(node)
        lo = getattr(stmt, "lineno", node.lineno)
        hi = getattr(stmt, "end_lineno", lo)
        for line in range(lo - 1, hi + 1):
            for rid, reason in self.suppressions.get(line, ()):
                if rid == rule:
                    self.used_suppressions.add((line, rule))
                    return (reason,)
        return None

    def meta_findings(self) -> list:
        """A bare allow with no reason never suppresses — report it."""
        out = []
        for line, entries in sorted(self.suppressions.items()):
            for rid, reason in entries:
                if not reason:
                    out.append(Finding(
                        "allow-missing-reason", self.relpath, line,
                        f"suppression allow[{rid}] carries no reason "
                        f"string — every allow must say why"))
                elif rid not in RULE_FAMILIES:
                    out.append(Finding(
                        "allow-missing-reason", self.relpath, line,
                        f"suppression names unknown rule id [{rid}]"))
        return out

    def stale_findings(self, strict: bool = False) -> list:
        """The stale-suppression audit: a reasoned ``allow[rule]`` whose
        rule no longer fires on its statement suppresses nothing — it is
        dead weight that silently blesses FUTURE violations on that
        line. Warning tier by default; ``--strict-suppressions``
        promotes to a gate-failing finding. Runs AFTER every rule has
        consumed its suppressions."""
        out = []
        for line, entries in sorted(self.suppressions.items()):
            for rid, reason in entries:
                if not reason or rid not in RULE_FAMILIES:
                    continue              # allow-missing-reason's problem
                if (line, rid) not in self.used_suppressions:
                    out.append(Finding(
                        "allow-stale", self.relpath, line,
                        f"suppression allow[{rid}] no longer matches a "
                        f"finding on this statement — drop it (or fix "
                        f"the drift that moved the finding)",
                        warning=not strict))
        return out

    # ---- structure --------------------------------------------------------

    def _index_functions(self) -> None:
        def visit(node, parent_fn, class_name, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    info = FunctionInfo(child, child.name, qual,
                                        parent_fn, class_name)
                    self.functions.append(info)
                    self._fn_of_node[id(child)] = info
                    visit(child, info, class_name, qual + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, parent_fn, child.name,
                          prefix + child.name + ".")
                else:
                    visit(child, parent_fn, class_name, prefix)
        visit(self.tree, None, None, "")

    def _index_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    self.import_aliases[alias.asname or alias.name] = \
                        f"{node.module}.{alias.name}"
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    self.import_aliases[alias.asname or
                                        alias.name.split(".")[0]] = \
                        alias.name

    def parent(self, node):
        return getattr(node, "_pl_parent", None)

    def ancestors(self, node):
        cur = self.parent(node)
        while cur is not None:
            yield cur
            cur = self.parent(cur)

    def enclosing_stmt(self, node):
        cur = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = self.parent(cur)
        return cur or node

    def enclosing_function(self, node) -> "FunctionInfo | None":
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return self._fn_of_node[id(anc)]
        return None

    def function_info(self, fn_node) -> "FunctionInfo | None":
        return self._fn_of_node.get(id(fn_node))

    def enclosing_chain(self, node):
        info = self.enclosing_function(node)
        while info is not None:
            yield info
            info = info.parent


def callee_dotted(call: ast.Call) -> str:
    """Best-effort dotted name of a call's callee ('' when dynamic)."""
    return dotted(call.func)


def dotted(node) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def last_name(node) -> str:
    d = dotted(node)
    return d.rsplit(".", 1)[-1] if d else ""


def apply_suppressions(ctx: ModuleContext, findings: list, nodes: list
                       ) -> list:
    """Pair rule findings with their AST nodes and mark the suppressed
    ones (reason recorded)."""
    for f, node in zip(findings, nodes):
        hit = ctx.suppression_for(f.rule, node)
        if hit is not None and hit[0]:
            f.suppressed = True
            f.suppress_reason = hit[0]
    return findings
