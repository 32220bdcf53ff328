"""In-process multi-node test cluster + disruption schemes.

Reference: test/test/InternalTestCluster.java:146 — N full Node instances in
one JVM over LocalTransport; test/test/disruption/ — NetworkPartition,
NetworkDisconnectPartition, NetworkDelaysPartition etc., installed by
swapping transport rules. This is the seam that makes Jepsen-style
distributed tests (DiscoveryWithServiceDisruptionsIT.java) run in-process.
"""

from __future__ import annotations

import tempfile
import time
from pathlib import Path

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.transport.local import DROP, LocalTransportHub


def _settled_use(breaker, timeout_s: float = 5.0) -> int:
    """A breaker's reserved bytes once work still in flight has had
    ``timeout_s`` to return them: a hedged search's losing copy may still
    run after its request answered, and releases when it stops. A leak
    never returns, so it still reads above zero."""
    deadline = time.monotonic() + timeout_s
    while breaker.used and time.monotonic() < deadline:
        time.sleep(0.02)
    return breaker.used


class InternalTestCluster:
    """N nodes sharing one LocalTransportHub. First node elects itself;
    the rest join. Fast fault-detection defaults so failover tests run in
    seconds."""

    DEFAULT_SETTINGS = {
        "fd.ping_interval": 0.1,
        "fd.ping_timeout": 0.3,
        "fd.ping_retries": 2,
        "discovery.zen.ping_timeout": 0.3,
        "discovery.zen.publish_timeout": 2.0,
        # a node joining a busy post-disruption cluster can need several
        # ping rounds under CI load; the default 30 s occasionally flakes
        "discovery.initial_state_timeout": 60.0,
    }

    def __init__(self, num_nodes: int = 3, base_path: str | Path | None = None,
                 settings: dict | None = None,
                 cluster_name: str = "test-cluster",
                 transport: str = "local"):
        """``transport``: "local" (in-process hub, the default) or "tcp"
        (real sockets on free loopback ports + unicast discovery) — the
        randomized matrix draws this so every suite exercises both wire
        paths (InternalTestCluster.java randomizes its transport the
        same way)."""
        self.transport = transport
        self.hub = LocalTransportHub() if transport == "local" else None
        self.base = Path(base_path or tempfile.mkdtemp(prefix="estpu-"))
        self.cluster_name = cluster_name
        self.settings = {**self.DEFAULT_SETTINGS, **(settings or {})}
        # quorum gate: without it, concurrent startup races let a node whose
        # first ping round beats its peers' transport registration elect
        # itself → permanent split-brain (ES requires minimum_master_nodes
        # for exactly this reason, elect/ElectMasterService.java)
        self.settings.setdefault("discovery.zen.minimum_master_nodes",
                                 num_nodes // 2 + 1)
        if transport == "tcp":
            import socket as _socket
            socks, ports = [], []
            for _ in range(num_nodes):
                s = _socket.socket()
                s.bind(("127.0.0.1", 0))
                socks.append(s)
                ports.append(s.getsockname()[1])
            for s in socks:
                s.close()
            self._tcp_ports = ports
            self.settings.update({
                "transport.type": "tcp",
                "discovery.zen.ping.unicast.hosts":
                    ",".join(f"127.0.0.1:{p}" for p in ports),
            })
            self.settings.setdefault("discovery.zen.publish_timeout", 3.0)
        self.nodes: list[Node] = []
        self._counter = 0
        # initial nodes start concurrently: with minimum_master_nodes > 1
        # no node can elect until a quorum of peers is pinging
        import threading
        pending = [self._make_node() for _ in range(num_nodes)]
        threads = [threading.Thread(target=n.start, daemon=True)
                   for n in pending]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        self.nodes.extend(pending)

    def _make_node(self, name: str | None = None, **extra_settings) -> Node:
        self._counter += 1
        # an explicit name re-uses that node's data path — the
        # killed-node-rejoins construction (dangling-indices tests)
        name = name or f"node-{self._counter}"
        settings = {**self.settings, **extra_settings,
                    "cluster.name": self.cluster_name, "node.name": name}
        if self.transport == "tcp":
            if self._counter <= len(self._tcp_ports):
                port = self._tcp_ports[self._counter - 1]
            else:                        # added node: grab a fresh port
                import socket as _socket
                s = _socket.socket()
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
                s.close()
                self._tcp_ports.append(port)
            settings["transport.tcp.port"] = port
        return Node(settings, data_path=self.base / name,
                    transport_hub=self.hub)

    # ---- membership --------------------------------------------------------

    def add_node(self, name: str | None = None, **extra_settings) -> Node:
        node = self._make_node(name=name, **extra_settings)
        node.start()
        self.nodes.append(node)
        return node

    def node(self, name: str) -> Node:
        for n in self.nodes:
            if n.node_name == name:
                return n
        raise KeyError(name)

    def master(self) -> Node:
        """The node that currently believes it is master (and is seen as
        master by a majority of live nodes). Right after a partition
        heals, a deposed master may claim mastership for one more fd
        ping interval — counting every live node's view keeps tests from
        addressing that second state lineage."""
        live = [n for n in self.nodes if n._started]
        claims = [n for n in live if n.is_master]
        # no single-claimant shortcut: right after a partition heals the
        # deposed minority master can briefly be the ONLY claimant (the
        # majority side mid-re-election claims nothing) — only majority
        # backing makes a claim real
        votes: dict[str, int] = {}
        for n in live:
            mid = n.cluster_service.state().master_node_id
            if mid is not None:
                votes[mid] = votes.get(mid, 0) + 1
        for n in claims:
            if votes.get(n.node_id, 0) > len(live) // 2:
                return n
        raise RuntimeError(f"no majority master (claims="
                           f"{[n.node_name for n in claims]}, "
                           f"votes={votes})")

    def non_masters(self) -> list[Node]:
        return [n for n in self.nodes if n._started and not n.is_master]

    def primary_node(self, index: str, shard: int) -> Node:
        """The node holding the primary copy of [index][shard]."""
        st = self.master().cluster_service.state()
        pr = st.routing_table.primary(index, shard)
        if pr is None or pr.node_id is None:
            raise RuntimeError(f"[{index}][{shard}] primary unassigned")
        for n in self.nodes:
            if n.node_id == pr.node_id:
                return n
        raise RuntimeError(f"primary node {pr.node_id} not in cluster")

    def stop_node(self, node: Node, graceful: bool = True) -> None:
        if graceful:
            node.close()
        else:
            node.kill()
        self.nodes.remove(node)

    def close(self, check_leaks: bool = True) -> None:
        leaks: list[str] = []
        if check_leaks:
            # the reference's test framework asserts resource balance at
            # cluster teardown (MockFSDirectoryService unclosed-handle
            # checks, AssertingSearcher leak ledger): after engines
            # close, every breaker reservation must have been returned
            for n in list(self.nodes):
                try:
                    for idx in getattr(n.indices_service, "indices",
                                       {}).values():
                        for engine in idx.engines.values():
                            engine.close()
                    bs = getattr(n, "breaker_service", None)
                    if bs is not None:
                        for bname in ("fielddata", "request"):
                            used = _settled_use(bs.breaker(bname))
                            if used:
                                leaks.append(
                                    f"node [{n.settings.get('node.name')}]"
                                    f" breaker [{bname}] leaked {used} "
                                    f"bytes after engine close")
                except Exception:                # noqa: BLE001 — teardown
                    pass
        for n in list(self.nodes):
            try:
                n.close()
            except Exception:                    # noqa: BLE001 — teardown
                pass
        self.nodes.clear()
        assert not leaks, "; ".join(leaks)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ---- waiting helpers ---------------------------------------------------

    def wait_for_nodes(self, count: int, timeout: float = 15.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            states = [n.cluster_service.state() for n in self.nodes
                      if n._started]
            if states and all(len(s.nodes) == count for s in states) and \
                    len({s.master_node_id for s in states}) == 1:
                return
            time.sleep(0.02)
        raise TimeoutError(
            f"cluster did not converge to {count} nodes; views: "
            f"{[(n.node_name, len(n.cluster_service.state().nodes)) for n in self.nodes if n._started]}")

    def wait_for_health(self, status: str = "green",
                        timeout: float = 15.0) -> dict:
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                last = self.master().cluster_service.state().health()
            except RuntimeError:
                time.sleep(0.05)
                continue
            want = {"green": ("green",),
                    "yellow": ("green", "yellow")}[status]
            if last["status"] in want:
                return last
            time.sleep(0.02)
        raise TimeoutError(f"health never reached {status}: {last}")

    def wait_converged_version(self, timeout: float = 10.0) -> None:
        """All live nodes hold the same state version."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            versions = {n.cluster_service.state().version
                        for n in self.nodes if n._started}
            if len(versions) == 1:
                return
            time.sleep(0.02)
        raise TimeoutError("state versions never converged")


# ---- disruption schemes (test/test/disruption/) ----------------------------

class NetworkPartition:
    """Split the cluster into two sides; messages across the cut are
    dropped in both directions (NetworkDisconnectPartition.java)."""

    def __init__(self, side_a: list[Node], side_b: list[Node]):
        self.side_a = side_a
        self.side_b = side_b

    def _install(self, nodes_from: list[Node], nodes_to: list[Node]) -> None:
        cut = {n.transport_service.local_node.address for n in nodes_to}
        for n in nodes_from:
            def rule(addr, action, _cut=cut):
                return DROP if addr in _cut else None
            n.transport_service.transport.outbound_rule = rule

    def start_disrupting(self) -> None:
        self._install(self.side_a, self.side_b)
        self._install(self.side_b, self.side_a)

    def stop_disrupting(self) -> None:
        for n in self.side_a + self.side_b:
            n.transport_service.transport.outbound_rule = None


class NetworkDelays:
    """Add latency to every outbound message of the given nodes
    (NetworkDelaysPartition.java)."""

    def __init__(self, nodes: list[Node], delay: float = 0.3):
        self.nodes = nodes
        self.delay = delay

    def start_disrupting(self) -> None:
        for n in self.nodes:
            n.transport_service.transport.outbound_rule = \
                lambda addr, action: self.delay

    def stop_disrupting(self) -> None:
        for n in self.nodes:
            n.transport_service.transport.outbound_rule = None


class ActionBlackhole:
    """Drop specific transport actions from a node (MockTransportService
    capability used by recovery/replication disruption tests)."""

    def __init__(self, node: Node, *action_prefixes: str):
        self.node = node
        self.prefixes = action_prefixes

    def start_disrupting(self) -> None:
        def rule(addr, action):
            if any(action.startswith(p) for p in self.prefixes):
                return DROP
            return None
        self.node.transport_service.transport.outbound_rule = rule

    def stop_disrupting(self) -> None:
        self.node.transport_service.transport.outbound_rule = None
