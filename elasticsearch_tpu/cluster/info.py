"""ClusterInfoService — live disk-usage / shard-size sampling.

Reference: core/cluster/InternalClusterInfoService.java — on the elected
master, periodically (cluster.info.update.interval, default 30s) fan out
node-stats and indices-stats requests, cache per-node disk usage and
per-shard sizes, and hand them to RoutingAllocation so the
DiskThresholdDecider decides from live data instead of an injected map.
A usage swing across the watermark triggers a reroute, the same way the
reference's listener fires one after a refresh.
"""

from __future__ import annotations

import threading


class ClusterInfoService:
    def __init__(self, node, interval_s: float = 30.0):
        self.node = node
        self.interval_s = interval_s
        self._timer: threading.Timer | None = None
        self._running = False
        # latest samples (read by stats APIs / tests)
        self.disk_usage: dict[str, float] = {}    # node_id → used fraction
        self.shard_sizes: dict[tuple, int] = {}   # (index, shard) → bytes
        self._last_over: frozenset = frozenset()

    def start(self) -> "ClusterInfoService":
        self._running = True
        self._schedule()
        return self

    def stop(self) -> None:
        self._running = False
        if self._timer is not None:
            self._timer.cancel()

    def _schedule(self) -> None:
        t = threading.Timer(self.interval_s, self._tick)
        t.daemon = True
        self._timer = t
        t.start()

    def _tick(self) -> None:
        try:
            self.refresh_once()
        except Exception:            # noqa: BLE001 — keep sampling
            pass
        if self._running:
            self._schedule()

    def refresh_once(self) -> None:
        """One sampling pass (InternalClusterInfoService.refresh): only
        the master samples — its RoutingAllocation is the one that
        allocates."""
        node = self.node
        state = node.cluster_service.state()
        if state.master_node_id != node.node_id:
            return
        # the file systems alone, as the reference's
        # NodesStatsRequest().clear().fs(true)
        stats = node.collect_nodes_stats(metrics=["fs"])
        usage: dict[str, float] = {}
        for nid, s in stats.get("nodes", {}).items():
            total = s.get("fs", {}).get("total", {})
            size = total.get("total_in_bytes", 0)
            free = total.get("free_in_bytes", 0)
            if size > 0:
                usage[nid] = 1.0 - free / size
        sizes: dict[tuple, int] = {}
        for name, svc in list(node.indices_service.indices.items()):
            for sid, engine in list(svc.engines.items()):
                try:
                    sizes[(name, sid)] = engine.store_size_bytes() \
                        if hasattr(engine, "store_size_bytes") else 0
                except Exception:    # noqa: BLE001 — engine closing
                    continue
        self.disk_usage = usage
        self.shard_sizes = sizes
        # the allocator reads this on every reroute from now on
        node.allocation.disk_usage = usage
        settings = {**state.persistent_settings, **state.transient_settings}
        # the LOW watermark is the threshold the DiskThresholdDecider
        # gates on (allocation.py) — crossings of THAT line change
        # allocation decisions and warrant a reroute
        low = float(settings.get(
            "cluster.routing.allocation.disk.watermark.low", 0.85))
        over = frozenset(nid for nid, u in usage.items() if u >= low)
        if over != self._last_over:
            # crossing the watermark (either direction) warrants a
            # reroute — shards may need to move off (or may fit again)
            self._last_over = over
            try:
                node.cluster_service.submit_state_update(
                    "cluster-info watermark change",
                    lambda st: node.allocation.reroute(
                        st, "disk watermark change"))
            except RuntimeError:
                pass                 # shutting down
