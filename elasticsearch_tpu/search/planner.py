"""Cost-driven query planner: one admission surface for the compiled
batch arms, one fallback taxonomy, one dispatch per plan.

Before this module, lane choice was a hardcoded N×N decline matrix:
the collective plane declined to the impact lane (``impact-preferred``)
and to the knn lane (``knn-lane``), and ``query_phase_batch_launch``
walked a fixed knn → impact → exact ladder with each arm screening the
next. Every new lane meant another row of pairwise rules. The planner
replaces that with plan composition:

* :func:`plan_batch` decomposes an admitted batch into candidate
  :class:`PlanNode` arms — each a lane-served sub-plan whose ``launch``
  thunk composes ALL of the request's work into ONE compiled dispatch
  (hybrid BM25+MaxSim+RRF fusion, impact candidate generation feeding a
  device-side rescore stage, knn ``filter`` masks resolved in-program).
* Each candidate is priced with
  :func:`~elasticsearch_tpu.observability.costs.estimate` (live EWMA
  when the lane has dispatched, XLA static analysis when cold — the
  typed ``cold`` flag rides the plan so pricing confidence is
  observable), and arms of equal admission specificity order by price.
* :func:`launch_plan` walks the priced arms, opens a ``plan.*`` span
  per node attempt (plane-lint's ``plan-node-spans`` family keeps every
  constructor site honest), and wraps the winning drain handle so
  :meth:`ShardSearcher.query_phase_batch_drain` can stamp
  predicted-vs-measured plan cost on profiled responses and flight-
  record mispriced plans.

Admission semantics are unchanged by pricing: arms keep their own
eligibility screens and tiers encode result-domain precedence (a knn
section can only be served by the vector lane; an impact-opted-in index
serves eligible shapes from the quantized columns deterministically —
cost never flips a batch between score DOMAINS, only between arms that
produce identical results). The cost signal decides the genuinely
interchangeable choices: mesh-vs-impact routing for the collective
plane (:func:`route_plane`) and equal-tier arm order.

Fallback taxonomy (the ``planner`` lane in ``search/lanes.py``):
``routed-impact`` / ``routed-knn`` replace the retired pairwise decline
edges, ``breaker-open`` covers candidates excluded because the device
is unhealthy or quarantined, ``no-plan`` is a batch with no admissible
compiled arm (the caller's serial path serves it), and ``plan-error``
is the planner's own defensive seam.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

from elasticsearch_tpu.observability import costs, tracing

__all__ = ["PlanNode", "Plan", "plan_batch", "launch_plan",
           "finish_plan", "route_plane", "order_nodes",
           "prefer_mesh_serving"]


@dataclass
class PlanNode:
    """One lane-served sub-plan arm of a candidate plan.

    ``span`` is the ``plan.``-prefixed span name opened around the
    node's launch attempt and ``fallback`` the registered ``planner``
    reason noted if the node errors out of the plan — both must be
    string literals at every constructor site (plane-lint
    ``plan-node-spans``). ``launch`` returns a drain handle or None
    (the node's own admission screen declined; the next arm runs).
    ``tier`` encodes admission specificity: lower tiers serve shapes
    the later tiers cannot (or serve them in a different, opted-in
    score domain), so cost ordering applies only WITHIN a tier."""

    lane: str                                  # program lane it dispatches on
    span: str                                  # "plan.<...>" span name
    fallback: str                              # registered planner reason
    launch: Callable[[], Any] | None = None
    tier: int = 0
    cost: "costs.CostEstimate | None" = None
    detail: str = ""

    @property
    def cold(self) -> bool:
        return bool(self.cost is None or self.cost.cold)


@dataclass
class Plan:
    """An ordered list of candidate arms for one admitted batch."""

    nodes: list = field(default_factory=list)

    @property
    def cold(self) -> bool:
        """True when NO candidate was priced from a measured EWMA —
        the whole plan rests on static analysis (or no estimate at
        all); ``jit_exec.note_planner_plan`` counts such plans apart."""
        return all(n.cold for n in self.nodes)

    @property
    def predicted_us(self) -> float | None:
        """The chosen (first) arm's priced cost, if any."""
        for n in self.nodes:
            if n.cost is not None:
                return float(n.cost)
        return None


def order_nodes(nodes: list) -> list:
    """Stable plan order: admission tier first, then price within the
    tier (unpriced arms after priced ones — an arm we cannot price at
    all never outranks one we can), original order breaking ties."""
    return sorted(
        nodes,
        key=lambda n: (n.tier,
                       float(n.cost) if n.cost is not None
                       else float("inf")))


def _priced(lane: str, node_id=None,
            mesh=None) -> "costs.CostEstimate | None":
    """Lane-level price: the dispatch-weighted measured mean when the
    lane has served traffic on this node, the static-analysis mean when
    it has only compiled (``cold=True``), None when the cost observatory
    has never seen the lane. Shape-exact pricing needs the compiled
    program key, which only exists after the arm commits — lane-level
    is the honest pre-dispatch signal. ``mesh`` scopes the price to
    one pod-slice geometry (costs.estimate's mesh axis)."""
    try:
        return costs.estimate(lane, node_id=node_id, mesh=mesh)
    except Exception:            # noqa: BLE001 — pricing must never veto
        return None


def prefer_mesh_serving(lane: str) -> bool:
    """Geometry routing: serve this batch on the pod-slice mesh lane
    (``impact-mesh`` / ``knn-mesh``) or the single-chip lane?

    Only meaningful when a serving mesh is installed (False
    otherwise). Same pricing discipline as :func:`route_plane`: the
    installed mesh is the operator's opt-in default, so it wins
    UNLESS both arms carry dispatch-backed estimates (``measured`` /
    ``lane-mean`` — a static roofline never overrides the opt-in) and
    the single-chip arm is strictly cheaper than the mesh arm priced
    at the serving geometry. Bit-identity between the arms is proven
    by the mesh-equality suite, so routing is purely a cost decision —
    it can never change a response."""
    from elasticsearch_tpu.search import jit_exec
    mesh = jit_exec.serving_mesh()
    if mesh is None:
        return False
    mesh_lane = {"impact": "impact-mesh", "knn": "knn-mesh"}.get(lane)
    if mesh_lane is None:
        return False
    m = _priced(mesh_lane, mesh=mesh)
    if lane == "impact":
        single = _priced("impact-pruned") or _priced("impact-eager")
    else:
        single = _priced("knn")
    backed = ("measured", "lane-mean")
    if m is not None and single is not None and \
            m.source in backed and single.source in backed and \
            float(single) < float(m):
        return False             # measured single-chip win
    return True


def plan_batch(shard, reqs: list, n_real: int | None = None
               ) -> Plan | None:
    """Decompose one admitted batch into priced candidate arms.

    ``shard`` is the owning :class:`~elasticsearch_tpu.search.phase.
    ShardSearcher`; the node thunks close over its private lane
    launchers so each arm keeps its own admission screen (declines
    return None and the next arm runs — bit-identity with the
    sequential per-lane ladder is structural, not re-proven per query).
    Returns None when the breaker/quarantine excludes every compiled
    arm (``breaker-open``) or the planner itself fails
    (``plan-error``)."""
    from elasticsearch_tpu.search import jit_exec
    try:
        if not jit_exec.plane_breaker.allow() or \
                jit_exec.plane_breaker.quarantined:
            # an open breaker (or watchdog quarantine) excludes every
            # device candidate — there is no plan to price; the serial
            # path re-screens under the same gate and lands eager
            jit_exec.note_planner_fallback("breaker-open")
            return None
        nodes: list[PlanNode] = []
        if all(r.knn is not None for r in reqs):
            # vector/hybrid shapes: only the knn lane can serve a knn
            # section (lexical arms would silently drop it) — tier 0,
            # and the ONLY arm (the exact screen rejects knn bodies)
            nodes.append(PlanNode(
                lane="knn", span="plan.knn", fallback="plan-error",
                launch=lambda: shard._knn_batch_launch(reqs,
                                                       n_real=n_real),
                tier=0, cost=_priced("knn"),
                detail="fused lexical+vector+RRF, in-program filter"))
        else:
            if any(r.rescore for r in reqs):
                # impact candidate generation feeding the exact-window
                # rescore as a device-side stage: one composed dispatch
                # instead of a primary dispatch + a host rescore pass
                nodes.append(PlanNode(
                    lane="impact-rescore", span="plan.rescore",
                    fallback="plan-error",
                    launch=lambda: shard._rescore_batch_launch(
                        reqs, n_real=n_real),
                    tier=1, cost=_priced("impact-rescore"),
                    detail="impact candidates + in-program rescore"))
            # quantized impact arm before the exact arm: the index
            # OPTED IN to the quantized score domain, so precedence is
            # deterministic (tier, not price — price must never flip a
            # request between score domains)
            nodes.append(PlanNode(
                lane="impact-pruned", span="plan.impact",
                fallback="plan-error",
                launch=lambda: shard._impact_batch_launch(
                    reqs, n_real=n_real),
                tier=2, cost=_priced("impact-pruned") or
                _priced("impact-eager"),
                detail="quantized impact columns (opt-in)"))
            nodes.append(PlanNode(
                lane="reader-batch", span="plan.exact",
                fallback="plan-error",
                launch=lambda: shard._exact_batch_launch(
                    reqs, n_real=n_real),
                tier=3, cost=_priced("reader-batch"),
                detail="exact batched scorer"))
        return Plan(nodes=order_nodes(nodes))
    except Exception:            # noqa: BLE001 — planner defensive seam
        jit_exec.note_planner_fallback("plan-error")
        return None


def launch_plan(plan: Plan):
    """Walk the plan's arms in order under per-node ``plan.*`` spans;
    the first arm whose launch admits the batch wins and its handle is
    wrapped as ``("plan", node, plan, t0)``+handle so the drain can
    stamp predicted-vs-measured plan cost. QueryParsingError propagates
    (a 400 is a request error on EVERY arm, never a fallback); any
    other arm explosion notes the node's fallback reason and the next
    arm runs — the plan absorbs a broken arm the way the old ladder
    absorbed a device error."""
    from elasticsearch_tpu.common.errors import QueryParsingError
    from elasticsearch_tpu.search import jit_exec
    for node in plan.nodes:
        t0 = time.perf_counter()
        with tracing.span(node.span, lane=node.lane,
                          predicted_us=None if node.cost is None
                          else round(float(node.cost), 1),
                          cold=node.cold):
            try:
                handle = node.launch()
            except QueryParsingError:
                raise
            except Exception as e:   # noqa: BLE001 — arm seam
                # the arm's own seam normally eats device errors and
                # returns None; anything escaping it is a planner-level
                # arm failure — note it and keep walking the plan
                jit_exec.note_fallback(e, reason="device-error")
                jit_exec.note_planner_fallback("plan-error")
                handle = None
        if handle is not None:
            jit_exec.note_planner_plan(len(plan.nodes), cold=plan.cold)
            return ("plan", node, plan, t0, handle)
    jit_exec.note_planner_fallback("no-plan")
    return None


#: measured/predicted ratio beyond which a served plan is flight-
#: recorded as mispriced (same spirit as the cost observatory's
#: dispatch-overrun anomaly threshold)
MISPRICE_RATIO = 4.0


def finish_plan(node: PlanNode, plan: Plan, t0: float) -> dict:
    """Drain-side accounting for a served plan: measured wall µs from
    launch to drained results vs the planner's predicted price, stamped
    on the drain-side ``plan.cost`` span (profiled responses carry it
    in the shard span tree) and flight-recorded as ``plan-mispriced``
    when a WARM prediction missed by :data:`MISPRICE_RATIO`."""
    measured_us = (time.perf_counter() - t0) * 1e6
    predicted = plan.predicted_us
    attrs = {"lane": node.lane, "cold": plan.cold,
             "measured_us": round(measured_us, 1)}
    if predicted is not None:
        attrs["predicted_us"] = round(predicted, 1)
        attrs["cost_error"] = round(
            measured_us / predicted if predicted > 0 else 0.0, 3)
    with tracing.span("plan.cost", **attrs):
        pass
    if predicted is not None and not plan.cold and predicted > 0 and \
            measured_us / predicted >= MISPRICE_RATIO:
        from elasticsearch_tpu.observability import flightrec
        flightrec.note("plan-mispriced", lane=node.lane,
                       predicted_us=round(predicted, 1),
                       measured_us=round(measured_us, 1))
    return attrs


def route_plane(indices, impact_eligible: bool, has_knn: bool
                ) -> str | None:
    """Collective-plane routing decision, replacing the pairwise
    ``impact-preferred`` / ``knn-lane`` decline edges: returns the lane
    the batch is routed onto (the plane declines) or None (the mesh
    keeps it).

    knn sections ALWAYS route — the mesh program has no vector or
    fusion lanes, so serving them there would drop the section. An
    impact-eligible batch routes to the impact lane (the opted-in
    sublinear arm) unless the cost observatory has MEASURED dispatch
    traffic on both arms (``measured`` / ``lane-mean`` estimates — a
    lane-level price is at best a dispatch-weighted mean, never an
    exact-shape EWMA) and the mesh is strictly cheaper — a static
    roofline estimate never overrides the opt-in default."""
    from elasticsearch_tpu.search import jit_exec
    if has_knn:
        jit_exec.note_planner_fallback("routed-knn")
        for index in indices:
            index.note_plane_fallback("routed-knn")
        return "knn"
    if impact_eligible:
        mesh = _priced("mesh")
        imp = _priced("impact-pruned") or _priced("impact-eager")
        backed = ("measured", "lane-mean")
        if mesh is not None and imp is not None and \
                mesh.source in backed and imp.source in backed and \
                float(mesh) < float(imp):
            return None          # measured mesh win: keep the plane
        jit_exec.note_planner_fallback("routed-impact")
        for index in indices:
            index.note_plane_fallback("routed-impact")
        return "impact"
    return None
