"""Lane-admission registry: the single source of truth for the four
compiled serving lanes' fallback vocabularies, their pairwise decline
edges, and the stats counters the lanes bump.

Everything in this module is a PLAIN LITERAL on purpose: plane-lint's
whole-program pass parses this file's AST (rule families
``counter-discipline`` and ``fallback-taxonomy``) and the
``estpu-lint --emit-lane-graph`` extractor emits it — together with the
source locations of every admission predicate and reason-labeled
decline site — as ``analysis/lane_graph.json``, the machine-readable
lane model the unified-planner refactor (ROADMAP item 3) consumes. A
tier-1 test (tests/test_lane_graph.py) round-trips the emitted graph
against these live registries, so registry, runtime and artifact cannot
drift apart.

Runtime consumers:

* :mod:`elasticsearch_tpu.search.jit_exec` initializes its ``_stats`` /
  ``_data_layer`` counter stores from :data:`JIT_COUNTERS` /
  :data:`DATA_LAYER_COUNTERS` (so every registered counter is surfaced
  through ``cache_stats`` → ``_nodes/stats`` by construction) and
  asserts every ``note_*_fallback`` reason against
  :data:`LANE_REASONS`;
* :mod:`elasticsearch_tpu.search.percolator` initializes each
  registry's ``stats`` dict from :data:`PERCOLATE_COUNTERS`.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# Counters: every key must be bumped somewhere (plane-lint
# counter-discipline flags orphans in BOTH directions: a bump of an
# unregistered key, and a registered key nothing bumps).
# ---------------------------------------------------------------------------

#: jit_exec._stats — the compiled-path program/cache/lane counters
#: surfaced verbatim under ``_nodes/stats`` ``indices.jit``.
JIT_COUNTERS = {
    "hits": "per-segment program cache hits",
    "misses": "per-segment program cache misses (one trace+compile)",
    "fallbacks": "compiled-program executions degraded to eager",
    "mesh_program_hits": "collective-plane program-layer cache hits",
    "mesh_program_misses": "collective-plane program trace+compiles",
    "plane_fallbacks": "collective-plane admission declines "
                       "(request served by the RPC fan-out)",
    "plane_items_served": "search items (an _msearch item counts one) "
                          "the collective plane answered",
    "plane_items_fallback": "items of plane admission declines (served "
                            "by the RPC fan-out; by reason in "
                            "plane_items_fallback_reasons)",
    "plane_dispatches": "collective-plane program dispatches (one a "
                        "served batch)",
    "plane_gather_bytes": "candidate bytes all_gathered over the shard "
                          "axis (shards x batch x k x bytes a dispatch)",
    "percolate_program_hits": "fused percolate lane program cache hits",
    "percolate_program_misses": "fused percolate lane trace+compiles",
    "breaker_open_skips": "requests the open plane breaker routed to "
                          "the fan-out/eager path (zero dispatches)",
    "oom_evictions": "HBM-OOM cold-block eviction sweeps",
    "oom_bytes_evicted": "device-block bytes freed by OOM sweeps",
    "impact_admissions": "requests served by the impact lane",
    "impact_blocks_scored": "impact blocks scored by the block-max sweep",
    "impact_blocks_skipped": "impact blocks skipped below the running "
                             "theta (the sublinearity evidence)",
    "impact_requant_refreshes": "impact requantizations forced by "
                                "cross-segment df drift",
    "knn_admissions": "requests served by the compiled knn lane",
    "knn_rows_real": "request rows the knn lane's dispatches carried",
    "knn_rows_padded": "no-op rows that padded knn dispatches to their "
                       "power-of-two batch bucket (scored, never "
                       "delivered)",
    "match_terms_real": "BM25 match query terms the real rows of "
                        "reader-batch dispatches scored",
    "match_terms_padded": "absent terms that padded those rows' term "
                          "lists to the batch's term bucket (compared "
                          "with every slot, never scoring)",
    "msearch_items_batched": "shard-side _msearch items answered by one "
                             "batched dispatch (query_phase_batch)",
    "msearch_items_serial": "shard-side _msearch items that fell to the "
                            "one-by-one query phase",
    "merge_items_array": "search items whose shard results the "
                         "coordinator merged by one array sort (score "
                         "order)",
    "merge_items_comparator": "search items the coordinator merged by "
                              "the field-sort comparator",
    "fusion_dispatches": "in-program hybrid fusion dispatches",
    "maxsim_dispatches": "fused MaxSim dispatches over rank_vectors",
    "rescore_fused_dispatches": "impact→rescore plans composed into one "
                                "device-side dispatch",
    # cost-driven query planner (search/planner.py): the single
    # admission surface over the compiled lanes
    "planner_plans": "batches the query planner priced and routed onto "
                     "a compiled arm",
    "planner_cold_plans": "plans priced on a cold estimate (static "
                          "analysis / lane aggregate, no measured EWMA)",
    "planner_fallbacks": "planner admission outcomes that left the "
                         "compiled arms (reason-labeled)",
    # continuous-batching scheduler (search/scheduler.py): the live
    # serving path's device feeder
    "scheduler_batches_launched": "micro-batches the continuous-batching "
                                  "scheduler dispatched",
    "scheduler_batches_drained": "scheduler batches whose device→host "
                                 "drain completed",
    "scheduler_requests_admitted": "requests served through scheduler "
                                   "batches (pad rows excluded)",
    "scheduler_requests_shed": "requests the scheduler shed "
                               "(deadline / SLO-burn / capacity)",
    "scheduler_pad_rows": "no-op pad rows appended to reach the pow2 "
                          "program bucket (never delivered or counted)",
    # dispatch watchdog (search/watchdog.py): stall detection on every
    # registered device wait
    "watchdog_stalls": "device waits that outlived their predicted "
                       "envelope (flight-recorded dispatch-stall)",
    "watchdog_abandoned": "stalled waits the watchdog abandoned (the "
                          "wedged program may still own the device)",
    "watchdog_quarantines": "quarantine entries after repeated stalls "
                            "(breaker held open, probe-gated reopen)",
    "watchdog_probe_reopens": "quarantines lifted by a successful "
                              "background probe program",
}

#: jit_exec._data_layer — incremental data-plane traffic accounting
#: (surfaced under ``indices.jit.data_layer`` and the per-index /
#: collective-plane mirrors).
DATA_LAYER_COUNTERS = {
    "bytes_uploaded": "host→device bytes (columns + live masks)",
    "bytes_reused": "resident-block column bytes composed, not re-sent",
    "col_bytes_uploaded": "column bytes uploaded",
    "mask_bytes_uploaded": "live-mask bytes uploaded",
    "incremental_refreshes": "rebuilds that uploaded O(new segment)",
    "full_rebuilds": "cold / changed-layout full pack builds",
    "mask_only_refreshes": "delete-only refreshes (zero column bytes)",
    "impact_bytes_uploaded": "impact-column bytes uploaded",
    "impact_bytes_reused": "resident impact-block bytes reused",
    "vector_bytes_uploaded": "knn vector-column bytes uploaded",
    "vector_bytes_reused": "resident vector-block bytes reused",
    "placement_bytes_uploaded": "placed mesh-lane block bytes shipped "
                                "to owning devices (delta refreshes "
                                "count changed shard slices only)",
    "placement_bytes_reused": "placed block bytes reused in place "
                              "(unchanged shard slices of a refresh)",
}

#: PercolatorRegistry.stats — per-index registry/evaluation counters
#: (surfaced via the ``_stats`` percolate section and `_nodes/stats`).
PERCOLATE_COUNTERS = {
    "builds": "registry constructions from scratch",
    "syncs": "metadata syncs that applied a change",
    "adds": "query registrations",
    "removes": "query unregistrations",
    "bucket_invalidations": "shape buckets touched by syncs",
    "mapper_rebuilds": "scratch MapperService rebuilds",
    "count": "percolate ops (one per probe doc)",
    "time_ms": "wall milliseconds in percolate ops",
    "fused_queries": "query evaluations on the fused device lane",
    "fallback_queries": "query evaluations on the per-query eager lane",
    "breaker_skips": "fused dispatches the open breaker routed eager",
}

#: the program lanes of the cost observatory — one per compiled-program
#: class (every ``jit_exec.observed_compile`` call names one; plane-lint
#: rule ``program-cost-unknown-lane`` checks the literals). These are
#: PROGRAM classes, finer than the four serving lanes: the planner costs
#: "impact-pruned at this shape", not "the impact lane".
PROGRAM_LANES = (
    "segment",          # run_segment: one query × one device segment
    "reader-batch",     # run_reader_batch: whole-reader fused program
    "streamed",         # run_segments_streamed: host-pool segment sweep
    "percolate",        # run_percolate_lanes: fused percolate groups
    "impact-eager",     # run_impact_batch: quantized eager impacts
    "impact-pruned",    # run_impact_pruned: block-max sweep
    "impact-rescore",   # run_impact_rescore: impact candidates + fused
                        # device-side rescore stage, one dispatch
    "knn",              # run_knn_hybrid_batch: vector/hybrid programs
    "mesh",             # mesh_engine._program: the collective plane
    "impact-mesh",      # run_impact_mesh: pod-slice block-max sweep
                        # (per-shard sweeps + θ-exchange + cross-chip
                        # top-k merge, one shard_map program)
    "knn-mesh",         # run_knn_hybrid_mesh: doc-sharded vector/
                        # MaxSim scoring + cross-chip candidate merge
)

#: the program cost observatory's per-lane gauge registry — the
#: OpenMetrics exposition renders one ``estpu_program_cost_<key>{lane=}``
#: gauge per entry from ``costs.lane_rollup()`` (whose rollup dicts
#: carry exactly these keys), so adding a field here adds it to the
#: scrape by construction. Emitted into ``lane_graph.json`` next to the
#: counter registries — the planner reads the lanes' observable cost
#: surface from the same artifact as their admission model.
PROGRAM_COST = {
    "resident": "programs resident in the cost table",
    "compiles": "program trace+compiles (sum over resident programs)",
    "compile_ms": "wall milliseconds spent compiling",
    "dispatches": "program dispatches recorded",
    "device_time_us": "accumulated device time (µs, span-measured)",
    "requests": "real requests served (the n_real contract)",
    "rows": "program batch rows dispatched (incl. pow2 padding)",
    "predicted_us": "dispatch-weighted roofline prediction (µs)",
    "measured_us": "dispatch-weighted measured EWMA (µs)",
}

# ---------------------------------------------------------------------------
# Fallback taxonomy: ONE registered reason vocabulary per lane.
# note_plane_fallback / note_impact_fallback / note_knn_fallback /
# note_percolate_fallback assert membership at runtime; plane-lint's
# fallback-taxonomy rule checks every literal call site statically and
# flags unknown, duplicated, and never-noted reasons.
# ---------------------------------------------------------------------------

LANE_REASONS = {
    # collective plane (mesh) admission declines, search_action
    "plane": (
        "ineligible-shape",     # sort/agg/cursor shape the mesh can't serve
        "parse-error",          # body failed the plane's re-parse
        "refresh-race",         # pack vs fetch-reader generation raced twice
        "device-error",         # mesh build/dispatch raised: eager rescue
        "not-local",            # not every target shard lives on this node
        "breaker-open",         # plane breaker open: zero-dispatch decline
        "device-stall",         # watchdog abandoned a wedged device wait
        "routed-impact",        # planner priced the impact arm cheaper
        "routed-knn",           # planner routed the knn lane (knn never
                                # rides the mesh)
    ),
    # impact-ordered lane admission declines, phase._impact_batch_launch
    "impact": (
        "dfs-stats",            # DFS global idf vs reader-local impacts
        "streamed-reader",      # non-resident segments can't pack impacts
        "ineligible-shape",     # aggs/sort/rescore/... shape screen
        "ineligible-cursor",    # search_after arity the lane can't resume
        "ineligible-query",     # not an impact-scorable term disjunction
        "mixed-fields",         # batch spans more than one impact field
        "no-impact-columns",    # opted in but no segment built impacts
        "cross-lane-cursor",    # cursor minted outside the quantized lane
        "device-error",         # impact pack/dispatch raised: exact rescue
    ),
    # dense / late-interaction lane declines, phase._knn_batch_launch
    "knn": (
        "mixed-shapes",         # batch spans fields/modes/plan signatures
        "streamed-reader",      # non-resident segments can't pack vectors
        "no-vector-columns",    # mapped but no segment carries vectors
        "device-error",         # vector pack/dispatch raised: eager rescue
        "breaker-open",         # plane breaker open: straight to eager
    ),
    # fused percolate lane declines, percolator.PercolatorRegistry.run
    "percolate": (
        "device-error",         # fused dispatch raised: eager rescue
        "breaker-open",         # plane breaker open: eager lane serves
    ),
    # continuous-batching scheduler sheds, scheduler.submit / pickup
    "scheduler": (
        "queue-deadline",       # deadline blown while queued: serial path
        "task-cancelled",       # task cancelled while queued: abort
        "slo-shed",             # queue_wait SLO burn: typed 429 rejection
        "queue-full",           # admission queue at capacity: typed 429
        "closed",               # node shutting down: serial fallback
        "device-stall",         # batch abandoned by the dispatch
                                # watchdog: waiters redirected serial
    ),
    # cost-driven query planner, planner.plan_batch — the single
    # admission surface that replaced the pairwise decline edges: the
    # plane no longer hardcodes "impact-preferred"/"knn-lane" handoffs,
    # it asks the planner which priced arm serves the request
    "planner": (
        "routed-impact",        # plan chose the impact arm over the mesh
        "routed-knn",           # plan chose the vector/hybrid arm (the
                                # mesh program has no vector lanes)
        "breaker-open",         # breaker open/quarantined: every device
                                # candidate excluded from the plan
        "no-plan",              # no candidate sub-plan admissible: the
                                # serial per-request path serves
        "plan-error",           # planner raised: legacy admission order
                                # served the batch (degraded, counted)
    ),
}

#: (declining lane, serving lane, reason the decliner labels): the
#: pairwise admission-handoff edges. EMPTY since the cost-driven
#: planner (search/planner.py) replaced the hardcoded handoffs — lane
#: choice is one priced decision surfaced through the ``planner``
#: vocabulary above (``routed-impact`` / ``routed-knn``), not an N×N
#: decline matrix. The tuple stays registered so the lane-graph
#: artifact keeps recording "no pairwise edges" machine-checkably.
DECLINE_EDGES = ()

#: lane → "pkg-relative module path::Qualname" of the admission
#: predicate (the function whose declines bump that lane's reasons).
#: The lane-graph extractor resolves these to file:line against the
#: live tree, so a rename breaks the tier-1 round-trip loudly.
LANE_ADMISSIONS = {
    "plane": "elasticsearch_tpu/action/search_action.py"
             "::SearchActions._try_collective_plane",
    "impact": "elasticsearch_tpu/search/phase.py"
              "::ShardSearcher._impact_batch_launch",
    "knn": "elasticsearch_tpu/search/phase.py"
           "::ShardSearcher._knn_batch_launch",
    "percolate": "elasticsearch_tpu/search/percolator.py"
                 "::PercolatorRegistry.run",
    "scheduler": "elasticsearch_tpu/search/scheduler.py"
                 "::ContinuousBatchScheduler.submit",
    "planner": "elasticsearch_tpu/search/planner.py"
               "::plan_batch",
}


def check_reason(lane: str, reason: str) -> str:
    """Assert-style guard the ``note_*_fallback`` seams call: an
    unregistered reason is a programming error (the taxonomy is closed;
    plane-lint checks literals statically, this catches dynamic ones)."""
    assert reason in LANE_REASONS[lane], (
        f"unregistered {lane}-lane fallback reason {reason!r} — add it "
        f"to elasticsearch_tpu.search.lanes.LANE_REASONS[{lane!r}]")
    return reason
