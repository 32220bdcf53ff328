"""New allocation deciders + automatic rebalancing + ClusterInfoService.

Reference: core/cluster/routing/allocation/decider/ (the full 16-decider
set — this round adds ShardsLimit, SnapshotInProgress,
RebalanceOnlyWhenActive, ClusterRebalance, ConcurrentRebalance),
BalancedShardsAllocator.balance (automatic rebalancing via streaming
relocation), and core/cluster/InternalClusterInfoService.java (live disk
sampling feeding the DiskThresholdDecider).
"""

from __future__ import annotations

import time

import pytest

from elasticsearch_tpu.cluster.allocation import AllocationService
from elasticsearch_tpu.cluster.state import (
    ClusterState, IndexMetadata, RoutingTable, ShardRoutingState)
from elasticsearch_tpu.transport.service import (
    DiscoveryNode, TransportAddress)


def _state(n_nodes: int, indices: dict[str, IndexMetadata]) -> ClusterState:
    nodes = {f"n{i}": DiscoveryNode(f"n{i}", f"n{i}",
                                    TransportAddress("local", 9300 + i))
             for i in range(n_nodes)}
    routing = RoutingTable()
    for meta in indices.values():
        routing = routing.add_index(meta)
    return ClusterState(nodes=nodes, master_node_id="n0", indices=indices,
                        routing_table=routing)


def _start_all(alloc, state):
    for _ in range(10):
        init = [s for s in state.routing_table.shards
                if s.state == ShardRoutingState.INITIALIZING]
        if not init:
            return state
        state = alloc.apply_started_shards(state, init)
    return state


def test_shards_limit_per_node_index_setting():
    meta = IndexMetadata("lim", 4, 0, settings={
        "index.routing.allocation.total_shards_per_node": 1})
    alloc = AllocationService()
    state = alloc.reroute(_state(2, {"lim": meta}), "test")
    per_node: dict[str, int] = {}
    for s in state.routing_table.shards:
        if s.assigned:
            per_node[s.node_id] = per_node.get(s.node_id, 0) + 1
    # 2 nodes × limit 1 → only 2 of 4 shards place; none doubles up
    assert all(v <= 1 for v in per_node.values())
    assert len(state.routing_table.unassigned()) == 2


def test_cluster_wide_shards_limit():
    meta = IndexMetadata("lim2", 6, 0)
    alloc = AllocationService()
    base = _state(2, {"lim2": meta}).with_(persistent_settings={
        "cluster.routing.allocation.total_shards_per_node": 2})
    state = alloc.reroute(base, "test")
    per_node: dict[str, int] = {}
    for s in state.routing_table.shards:
        if s.assigned:
            per_node[s.node_id] = per_node.get(s.node_id, 0) + 1
    assert all(v <= 2 for v in per_node.values())


def test_automatic_rebalance_on_node_join():
    """All shards start on one node; when a second data node appears,
    reroute begins streaming relocations until balanced — gated to one
    in-flight move per pass by ConcurrentRebalance + the pass design."""
    meta = IndexMetadata("bal", 4, 0)
    alloc = AllocationService()
    state = alloc.reroute(_state(1, {"bal": meta}), "test")
    state = _start_all(alloc, state)
    assert all(s.node_id == "n0" for s in state.routing_table.shards)
    # second node joins
    nodes = dict(state.nodes)
    nodes["n1"] = DiscoveryNode("n1", "n1", TransportAddress("local", 9301))
    state = alloc.reroute(state.with_(nodes=nodes), "node joined")
    # drive relocations to completion
    for _ in range(10):
        targets = [s for s in state.routing_table.shards
                   if s.relocation_target]
        if not targets:
            break
        state = alloc.apply_started_shards(state, targets)
    counts = {}
    for s in state.routing_table.shards:
        counts[s.node_id] = counts.get(s.node_id, 0) + 1
    assert counts == {"n0": 2, "n1": 2}, counts
    assert all(s.state == ShardRoutingState.STARTED
               for s in state.routing_table.shards)


def test_rebalance_respects_concurrency_limit():
    meta = IndexMetadata("cc", 6, 0)
    alloc = AllocationService()
    state = alloc.reroute(_state(1, {"cc": meta}), "test")
    state = _start_all(alloc, state)
    nodes = dict(state.nodes)
    nodes["n1"] = DiscoveryNode("n1", "n1", TransportAddress("local", 9301))
    state = state.with_(nodes=nodes, persistent_settings={
        "cluster.routing.allocation.cluster_concurrent_rebalance": 1})
    # several reroutes without completing the first relocation: the cap
    # holds at one in-flight move
    for _ in range(3):
        state = alloc.reroute(state, "tick")
    relocating = [s for s in state.routing_table.shards
                  if s.state == ShardRoutingState.RELOCATING]
    assert len(relocating) == 1


def test_rebalance_disabled_by_setting():
    meta = IndexMetadata("off", 4, 0)
    alloc = AllocationService()
    state = alloc.reroute(_state(1, {"off": meta}), "test")
    state = _start_all(alloc, state)
    nodes = dict(state.nodes)
    nodes["n1"] = DiscoveryNode("n1", "n1", TransportAddress("local", 9301))
    state = state.with_(nodes=nodes, persistent_settings={
        "cluster.routing.rebalance.enable": "none"})
    state = alloc.reroute(state, "tick")
    assert not any(s.state == ShardRoutingState.RELOCATING
                   for s in state.routing_table.shards)


def test_snapshot_in_progress_blocks_rebalance():
    meta = IndexMetadata("snap", 4, 0)
    alloc = AllocationService()
    state = alloc.reroute(_state(1, {"snap": meta}), "test")
    state = _start_all(alloc, state)
    nodes = dict(state.nodes)
    nodes["n1"] = DiscoveryNode("n1", "n1", TransportAddress("local", 9301))
    # the exact shape SnapshotsService publishes (service.py:119)
    snap = {"repository": "r1", "snapshot": "s1", "state": "STARTED",
            "indices": ["snap"]}
    state = state.with_(nodes=nodes,
                        customs={"snapshots_in_progress": snap})
    state = alloc.reroute(state, "tick")
    assert not any(s.state == ShardRoutingState.RELOCATING
                   for s in state.routing_table.shards)


def test_disk_threshold_fed_by_cluster_info(tmp_path):
    """ClusterInfoService samples real fs stats on the master and feeds
    AllocationService.disk_usage without any caller injection."""
    from elasticsearch_tpu.node import Node
    with Node({"node.name": "cis"}, data_path=tmp_path) as n:
        n.indices_service.create_index("d", {"settings": {
            "number_of_shards": 1, "number_of_replicas": 0}})
        assert n.allocation.disk_usage == {}
        n.cluster_info_service.refresh_once()
        usage = n.allocation.disk_usage
        assert n.node_id in usage and 0.0 <= usage[n.node_id] <= 1.0
        assert ("d", 0) in n.cluster_info_service.shard_sizes


def test_cluster_info_samples_the_file_systems_alone(tmp_path, monkeypatch):
    """The periodic sample asks each node for its fs section only (the
    reference's NodesStatsRequest().clear().fs(true)): the whole stats
    document is never built for it."""
    from elasticsearch_tpu.node import Node
    with Node({"node.name": "cis-fs"}, data_path=tmp_path) as n:
        def whole(*a, **k):
            raise AssertionError("the whole stats document was built")
        monkeypatch.setattr(n, "local_node_stats", whole)
        n.cluster_info_service.refresh_once()
        usage = n.allocation.disk_usage
        assert n.node_id in usage and 0.0 <= usage[n.node_id] <= 1.0
        monkeypatch.undo()
        assert "fs" in n.collect_nodes_stats()["nodes"][n.node_id]
