"""Node — the service container and lifecycle.

Reference: core/node/Node.java:129-315 — module assembly (:161-198), ordered
start (:230-275: indices → cluster → search → discovery → gateway → http).
One Node owns: persisted cluster state (gateway), ClusterService,
IndicesService (reconciler), SearchService, and the document/bulk action
entry points (the action layer, core/action/) that the REST layer and the
Python client both call — mirroring how NodeClient and RestController share
TransportAction instances.
"""

from __future__ import annotations

import threading as _threading
import time
import uuid
from pathlib import Path

from elasticsearch_tpu.cluster.allocation import AllocationService
from elasticsearch_tpu.cluster.service import URGENT, ClusterService
from elasticsearch_tpu.cluster.state import (
    ClusterState, IndexMetadata, RoutingTable)
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.transport import (
    DiscoveryNode, LocalTransport, LocalTransportHub, TransportService)
from elasticsearch_tpu.transport.service import TransportAddress


class Node:
    def __init__(self, settings: Settings | dict | None = None,
                 data_path: str | Path | None = None,
                 transport_hub: LocalTransportHub | None = None):
        if not isinstance(settings, Settings):
            settings = Settings(settings or {})
        # plugin scan + default-settings merge happen before anything reads
        # settings (reference order: PluginsService at core/node/Node.java:145
        # precedes module assembly; plugin additionalSettings merge UNDER
        # user settings)
        from elasticsearch_tpu.plugins import PluginsService
        specs = settings.get("plugins") or []
        if isinstance(specs, str):
            specs = [s.strip() for s in specs.split(",") if s.strip()]
        self.plugins_service = PluginsService(specs)
        defaults = self.plugins_service.merged_default_settings()
        if defaults:
            settings = Settings(defaults).merge(settings)
        self.settings = settings
        self.node_id = uuid.uuid4().hex[:20]
        self.node_name = settings.get("node.name", f"node-{self.node_id[:7]}")
        self.data_path = Path(data_path or settings.get("path.data", "data"))
        self.data_path.mkdir(parents=True, exist_ok=True)
        self._hub = transport_hub
        self._started = False
        self.serving_mesh = None         # search.mesh, installed at start

    # ---- lifecycle (Node.start order, core/node/Node.java:230-275) ---------

    SHARD_STARTED_ACTION = "internal:cluster/shard/started"
    SHARD_FAILED_ACTION = "internal:cluster/shard/failure"

    def start(self) -> "Node":
        # compiled programs persist across processes from the first one
        # on; a device without roofline peaks fails here, not per request
        from elasticsearch_tpu.common.device import ensure_compile_cache
        from elasticsearch_tpu.observability import costs
        ensure_compile_cache()
        costs.machine_constants()
        self.serving_mesh = self._install_serving_mesh()
        # transport selection (ref: `transport.type` setting resolved by
        # NetworkModule — NettyTransport by default, LocalTransport for
        # embedded/test use; core/node/Node.java:230-275 wiring order).
        # "tcp" boots a real socket server so multi-process / multi-host
        # clusters form over the network; "local" keeps the in-process hub.
        transport_type = self.settings.get("transport.type", "local")
        if transport_type in ("tcp", "netty"):
            from elasticsearch_tpu.transport.tcp import TcpTransport
            hub = None
            transport = TcpTransport(
                self.settings.get("transport.host", "127.0.0.1"),
                self.settings.get_as_int("transport.tcp.port", 0),
                publish_host=self.settings.get("transport.publish_host"),
                compress=self.settings.get_as_bool(
                    "transport.tcp.compress", False))
            seed_provider = self._unicast_seeds
        elif transport_type == "local":
            hub = self._hub or LocalTransportHub()
            transport = LocalTransport(hub)
            seed_provider = hub.addresses
        else:
            raise ValueError(f"unknown transport.type [{transport_type}]")
        attrs = (("data", self.settings.get("node.data", "true")),
                 ("master", self.settings.get("node.master", "true")))
        # every other `node.<key>` setting becomes a custom node attribute
        # (ref: DiscoveryNode attributes from `node.` settings,
        # core/cluster/node/DiscoveryNodeService.java)
        reserved = {"data", "master", "name", "local", "mode", "client",
                    "max_local_storage_nodes", "portsfile"}
        extra = tuple(
            (k[len("node."):], str(v))
            for k, v in sorted(self.settings.as_dict().items())
            if k.startswith("node.") and k[len("node."):] not in reserved
            and "." not in k[len("node."):])
        attrs = attrs + extra
        from elasticsearch_tpu.common.threadpool import ThreadPool
        self.thread_pool = ThreadPool(self.settings)
        from elasticsearch_tpu import __version__ as _build
        self.transport_service = TransportService(
            transport,
            lambda addr: DiscoveryNode(self.node_id, self.node_name, addr,
                                       attributes=attrs, build=_build),
            thread_pool=self.thread_pool)
        # task registry (core/tasks/TaskManager.java): every inbound RPC
        # and every locally-spawned action registers under a
        # cluster-unique "node:seq" id; wired into the transport so the
        # parent link propagates on every outgoing request
        from elasticsearch_tpu.tasks import TaskManager
        self.task_manager = TaskManager(self.node_id, self.node_name)
        self.transport_service.task_manager = self.task_manager
        self.task_manager.ban_broadcaster = self._broadcast_task_ban
        self.transport_service.register_request_handler(
            self.TASKS_LIST_ACTION, self._handle_tasks_list,
            executor="management", sync=True)
        self.transport_service.register_request_handler(
            self.TASK_CANCEL_ACTION, self._handle_task_cancel,
            executor="management", sync=True)
        # bans apply inline on the delivery thread ("same"): a cancel
        # must land even when the management pool is saturated by the
        # very work being cancelled
        self.transport_service.register_request_handler(
            self.TASK_BAN_ACTION, self._handle_task_ban,
            executor="same", sync=True)
        self.allocation = AllocationService()
        cluster_name = self.settings.get("cluster.name", "elasticsearch-tpu")
        self.cluster_service = ClusterService(
            ClusterState(cluster_name=cluster_name), self.node_id)
        self.cluster_service.add_listener(self._persist_state)
        # orphan reaping: when a node leaves the cluster, every task
        # parented on it is cancelled (its coordinator can neither
        # collect nor cancel it anymore) and its bans are dropped
        self.cluster_service.add_listener(self._reap_tasks_on_node_left)
        from elasticsearch_tpu.indices.service import IndicesService
        from elasticsearch_tpu.common.breaker import (
            HierarchyCircuitBreakerService)
        self.breaker_service = HierarchyCircuitBreakerService(self.settings)
        # SLO targets (observability.slo.* settings) — installed once so
        # the histogram seam classifies good/bad from the first event
        from elasticsearch_tpu.observability import slo as _slo
        _slo.configure(self.node_id, self.settings)
        self.indices_service = IndicesService(self.data_path,
                                              self.cluster_service,
                                              self.node_id,
                                              self.allocation)
        self.indices_service.breaker_service = self.breaker_service
        self.indices_service.merge_submit = \
            lambda fn: self.thread_pool.submit("merge", fn)
        self.indices_service.on_shard_started = self._on_shard_started
        self.indices_service.on_shard_failed = self._on_shard_failed
        # ShardStateAction RPC endpoints (master side)
        self.transport_service.register_request_handler(
            self.SHARD_STARTED_ACTION, self._handle_shard_started, sync=True)
        self.transport_service.register_request_handler(
            self.SHARD_FAILED_ACTION, self._handle_shard_failed, sync=True)
        # master-forwarding seam (TransportMasterNodeAction analog)
        self.indices_service.master_executor = self._execute_master_action
        # dangling-indices offer path (DanglingIndicesState → master
        # metadata re-import + allocation)
        self.indices_service.dangling_import = self._import_dangling
        self.transport_service.register_request_handler(
            self.MASTER_FORWARD_ACTION, self._handle_master_forward,
            executor="management", sync=True)
        # distributed action layer (core/action/)
        from elasticsearch_tpu.action import (
            BroadcastActions, DocumentActions, SearchActions)
        self.document_actions = DocumentActions(self)
        self.search_actions = SearchActions(self)
        self.broadcast_actions = BroadcastActions(self)
        # collective-plane data-layer pipelining: engine reader swaps
        # (refresh/merge) schedule the next-generation pack build off
        # the query hot path; per-index request_cache stats read the
        # node's shard request cache through the same late-bound seam
        self.indices_service.reader_swap_hook = \
            self.search_actions.schedule_plane_rebuild
        self.indices_service.request_cache = \
            self.search_actions.request_cache
        # peer recovery (core/indices/recovery/): replicas pull files + ops
        # from their active primary before reporting started
        from elasticsearch_tpu.indices.recovery import PeerRecoveryService
        self.recovery_service = PeerRecoveryService(self)
        self.indices_service.prepare_shard = \
            self.recovery_service.recover_shard
        # snapshot/restore (core/snapshots/)
        from elasticsearch_tpu.snapshots import SnapshotsService
        self.snapshots_service = SnapshotsService(self)
        # live disk-usage sampling feeding the DiskThresholdDecider
        # (InternalClusterInfoService) — constructed here, started at the
        # end of start() so a failed boot never leaks the timer
        from elasticsearch_tpu.cluster.info import ClusterInfoService
        from elasticsearch_tpu.common.settings import parse_time_value \
            as _ptv
        self.cluster_info_service = ClusterInfoService(
            self, interval_s=_ptv(
                self.settings.get("cluster.info.update.interval", "30s"),
                "cluster.info.update.interval"))
        # node-level monitoring fan-out (core/action/admin/cluster/node/)
        self.transport_service.register_request_handler(
            self.NODE_STATS_ACTION, self._handle_node_stats,
            executor="management", sync=True)
        self.transport_service.register_request_handler(
            self.HOT_THREADS_ACTION, self._handle_hot_threads,
            executor="management", sync=True)
        self.transport_service.register_request_handler(
            self.TRACE_COLLECT_ACTION, self._handle_trace_collect,
            executor="management", sync=True)
        self._delayed_reroute_timer = None
        self.cluster_service.add_listener(self._schedule_delayed_reroute)
        # TTL purger (IndicesTTLService): periodic sweep deleting expired
        # _ttl docs through the normal replicated delete path
        from elasticsearch_tpu.common.settings import parse_time_value
        self._ttl_interval = parse_time_value(
            self.settings.get("indices.ttl.interval", "60s"), "ttl.interval")
        self._ttl_timer = None
        self._schedule_ttl_sweep()
        # IndexingMemoryController (core/indices/memory/
        # IndexingMemoryController.java:48): a node-wide budget for
        # uncommitted write buffers; when the sum exceeds
        # indices.memory.index_buffer_size, the largest buffers refresh
        # (turning them into searchable segments frees the RAM)
        self._index_buffer_budget = self._parse_buffer_size(
            self.settings.get("indices.memory.index_buffer_size", "10%"))
        self._imc_timer = None
        self._schedule_imc()
        # file scripts hot-reload (ResourceWatcherService + the
        # ScriptService file-script listener)
        from elasticsearch_tpu.watcher import ResourceWatcherService
        scripts_dir = Path(self.settings.get(
            "path.scripts", self.data_path / "config" / "scripts"))
        self.resource_watcher = ResourceWatcherService(
            scripts_dir,
            interval_s=parse_time_value(
                self.settings.get("resource.reload.interval", "5s"),
                "resource.reload.interval")).start()
        # plugin ZenPing providers compose with the transport's own seed
        # source (DiscoveryModule.addZenPing — how discovery-multicast
        # rides beside unicast); collected BEFORE ZenDiscovery starts so
        # plugin seeds feed the initial election round
        try:
            extra_pings = self.plugins_service.collect_zen_pings(self)
            if extra_pings:
                base_seeds = seed_provider

                def seed_provider():
                    seeds = list(base_seeds())
                    seen = set(seeds)
                    for fn in extra_pings:
                        # plugin seeds are best-effort ADDITIONS: one
                        # failing probe must not cost the round its
                        # unicast seeds
                        try:
                            extra = fn()
                        except Exception:    # noqa: BLE001 — next round
                            continue
                        for a in extra:
                            if a not in seen:
                                seen.add(a)
                                seeds.append(a)
                    return seeds
            from elasticsearch_tpu.discovery import ZenDiscovery
            self.discovery = ZenDiscovery(
                self.transport_service, self.cluster_service,
                self.allocation,
                seed_provider=seed_provider, cluster_name=cluster_name,
                min_master_nodes=self.settings.get_as_int(
                    "discovery.zen.minimum_master_nodes", 1),
                gateway_fn=self._gateway_recover,
                ping_timeout=self.settings.get_as_float(
                    "discovery.zen.ping_timeout", 1.0),
                fd_interval=self.settings.get_as_float(
                    "fd.ping_interval", 0.5),
                fd_timeout=self.settings.get_as_float(
                    "fd.ping_timeout", 1.0),
                fd_retries=self.settings.get_as_int("fd.ping_retries", 3),
                publish_timeout=self.settings.get_as_float(
                    "discovery.zen.publish_timeout", 10.0))
        except Exception:
            # a failed boot must not leak plugin ping responders (same
            # invariant cluster_info_service keeps: constructed here,
            # started only once start() cannot fail before _started)
            self.plugins_service.abort_zen_pings(self)
            raise
        self._started = True
        self.discovery.start(self.settings.get_as_float(
            "discovery.initial_state_timeout", 30.0))
        self.cluster_info_service.start()
        # plugin service wiring once the node is fully up (the analog of
        # nodeServices()/onModule hooks firing at injector-creation time)
        self.plugins_service.apply_node_start(self)
        return self

    def _unicast_seeds(self) -> list[TransportAddress]:
        """Unicast discovery seeds for TCP clusters (ref: UnicastZenPing,
        `discovery.zen.ping.unicast.hosts` — a list or comma string of
        host:port pairs). The local bound address is implicit; zen skips it
        when pinging."""
        raw = self.settings.get("discovery.zen.ping.unicast.hosts") or []
        if isinstance(raw, str):
            raw = [h.strip() for h in raw.split(",") if h.strip()]
        seeds = []
        for entry in raw:
            entry = str(entry)
            if entry.startswith("["):
                # bracketed IPv6: [::1] or [::1]:9300
                host, _, rest = entry[1:].partition("]")
                port = rest.lstrip(":") or "9300"
            elif entry.count(":") > 1:
                # raw IPv6 literal, no port syntax possible
                host, port = entry, "9300"
            else:
                host, sep, port = entry.rpartition(":")
                if not sep or not port:
                    # bare host: default to the standard transport port
                    # (the reference appends :9300 to host-only entries)
                    host, port = entry.rstrip(":"), "9300"
            seeds.append(TransportAddress(host or "127.0.0.1", int(port)))
        return seeds

    def _gateway_recover(self, state: ClusterState) -> ClusterState:
        """Gateway recovery (GatewayMetaState): merge persisted metadata
        into the state when this node becomes master of a fresh cluster."""
        raw = ClusterState.load_metadata(self.data_path / "_state")
        if not raw:
            return state
        indices = dict(state.indices)
        routing = state.routing_table
        for name, m in raw.get("indices", {}).items():
            if name in indices:
                continue
            meta = IndexMetadata.from_state_dict(name, m)
            indices[name] = meta
            routing = routing.add_index(meta)
        from elasticsearch_tpu.indices.service import IndicesService
        tombs = list(raw.get("tombstones", []))
        for t in state.customs.get("index_tombstones", []):
            if t not in tombs:
                tombs.append(t)
        customs = dict(state.customs)
        if tombs:
            customs["index_tombstones"] = \
                tombs[-IndicesService.TOMBSTONE_CAP:]
        return state.with_(
            version=max(state.version, raw.get("version", 0)),
            indices=indices, routing_table=routing,
            templates={**raw.get("templates", {}), **state.templates},
            persistent_settings={**raw.get("persistent_settings", {}),
                                 **state.persistent_settings},
            customs=customs)

    # ---- master forwarding (TransportMasterNodeAction.java:50) -------------

    MASTER_FORWARD_ACTION = "cluster:admin/forward"

    def _execute_master_action(self, action: str, request: dict, local):
        """Run a metadata op on the elected master: locally when we are it,
        else forward over the transport and wait for the ack (the published
        state reaches us before the master responds, because publish acks
        gate the response — PublishClusterStateAction two-phase commit)."""
        from elasticsearch_tpu.action.replication import unwrap_remote
        from elasticsearch_tpu.common.errors import MasterNotDiscoveredError
        from elasticsearch_tpu.transport.service import (
            RemoteTransportError, TransportException)
        deadline = time.monotonic() + 30.0
        while True:
            state = self.cluster_service.state()
            if state.master_node_id == self.node_id or \
                    state.master_node is None and not self._started:
                return local()
            master = state.master_node
            if master is None:
                if time.monotonic() > deadline:
                    raise MasterNotDiscoveredError(
                        f"no master to forward [{action}] to")
                time.sleep(0.05)
                continue
            try:
                self.transport_service.send_request(
                    master, self.MASTER_FORWARD_ACTION,
                    {"action": action, "request": request},
                    timeout=30.0).result(35.0)
                return None
            except Exception as e:               # noqa: BLE001 — unwrap
                if isinstance(e, TransportException) and \
                        not isinstance(e, RemoteTransportError):
                    # master died mid-request: retry across elections
                    if time.monotonic() > deadline:
                        raise MasterNotDiscoveredError(
                            f"[{action}] failed: {e}") from None
                    time.sleep(0.1)
                    continue
                raise unwrap_remote(e) from None

    def put_stored_script(self, lang: str, sid: str, source) -> bool:
        """Indexed/stored scripts live in cluster state (the reference's
        hidden .scripts index; metadata storage gives the same durability
        — cf. search/templates.py's reasoning for stored templates).
        → created (False = overwrote), decided inside the MASTER's
        single-writer update so concurrent puts and applied-state lag on
        the coordinating node can't misreport it."""
        out = self.indices_service._master_op(
            "put-script", {"lang": lang, "id": sid, "source": source},
            lambda: self._put_script_on_master(lang, sid, source))
        return bool(out.get("created", True)) if isinstance(out, dict) \
            else True

    def delete_stored_script(self, lang: str, sid: str) -> None:
        self.indices_service._master_op(
            "delete-script", {"lang": lang, "id": sid},
            lambda: self._delete_script_on_master(lang, sid))

    def _put_script_on_master(self, lang: str, sid: str, source) -> dict:
        created = [True]
        version = [1]

        def update(state):
            key = f"{lang}\x00{sid}"
            existing = state.customs.get("stored_scripts", {})
            created[0] = key not in existing
            versions = dict(state.customs.get("stored_script_versions", {}))
            version[0] = versions.get(key, 0) + 1
            versions[key] = version[0]
            scripts = {**existing, key: source}
            return state.with_(customs={
                **state.customs, "stored_scripts": scripts,
                "stored_script_versions": versions})
        self.cluster_service.submit_and_wait(f"put-script [{sid}]", update)
        return {"created": created[0], "version": version[0]}

    def _delete_script_on_master(self, lang: str, sid: str) -> None:
        def update(state):
            key = f"{lang}\x00{sid}"
            scripts = {k: v for k, v in
                       state.customs.get("stored_scripts", {}).items()
                       if k != key}
            # deletion bumps the version like a document delete would
            # (the reference's .scripts index semantics)
            versions = dict(state.customs.get("stored_script_versions", {}))
            versions[key] = versions.get(key, 0) + 1
            return state.with_(customs={
                **state.customs, "stored_scripts": scripts,
                "stored_script_versions": versions})
        self.cluster_service.submit_and_wait(f"delete-script [{sid}]",
                                             update)

    def stored_script(self, sid: str, lang: str = "mustache"):
        src = self.cluster_service.state().customs.get(
            "stored_scripts", {}).get(f"{lang}\x00{sid}")
        if src is None and getattr(self, "resource_watcher", None):
            # file scripts resolve after indexed ones (ScriptService
            # lookup order: inline > indexed > file)
            src = self.resource_watcher.get(sid, lang)
        return src

    def stored_script_version(self, sid: str, lang: str) -> int:
        return self.cluster_service.state().customs.get(
            "stored_script_versions", {}).get(f"{lang}\x00{sid}", 0)

    def cluster_reroute(self, commands: list[dict],
                        dry_run: bool = False) -> dict:
        """POST /_cluster/reroute (ref: TransportClusterRerouteAction +
        allocation commands): explicit shard placement commands applied
        through the master's single-writer queue; dry_run validates and
        computes without publishing."""
        if dry_run:
            state = self.cluster_service.state()
            new = self.allocation.execute_commands(state, commands)
            return {"acknowledged": True,
                    "state": {"routing_table": new.routing_table.to_dict()
                              if hasattr(new.routing_table, "to_dict")
                              else {}}}
        self.indices_service._master_op(
            "cluster-reroute", {"commands": commands},
            lambda: self._reroute_on_master(commands))
        return {"acknowledged": True}

    def _reroute_on_master(self, commands: list[dict]) -> None:
        from elasticsearch_tpu.cluster.service import URGENT
        errors: list[Exception] = []

        def update(state):
            try:
                return self.allocation.execute_commands(state, commands)
            except Exception as e:           # noqa: BLE001 — surface below
                errors.append(e)
                return state
        self.cluster_service.submit_and_wait("cluster-reroute", update,
                                             priority=URGENT)
        if errors:
            raise errors[0]

    def _handle_master_forward(self, request: dict, source) -> dict:
        isvc = self.indices_service
        action, req = request["action"], request["request"]
        dispatch = {
            "create-index": lambda: isvc.create_index(req["name"],
                                                      req["body"]),
            "delete-index": lambda: isvc.delete_index(req["name"]),
            "put-mapping": lambda: isvc.put_mapping(req["name"], req["type"],
                                                    req["mapping"]),
            "update-settings": lambda: isvc.update_settings(req["name"],
                                                            req["settings"]),
            "put-alias": lambda: isvc.put_alias(req["index"], req["alias"],
                                                req.get("body")),
            "delete-alias": lambda: isvc.delete_alias(req["index"],
                                                      req["alias"]),
            "index-state": lambda: isvc.set_index_state(req["index"],
                                                        req["state"]),
            "put-warmer": lambda: isvc.put_warmer(req["index"], req["name"],
                                                  req["body"]),
            "delete-warmer": lambda: isvc.delete_warmers(
                req["index"], set(req["names"])),
            "put-template": lambda: self.put_template(req["name"],
                                                      req["body"]),
            "delete-template": lambda: self.delete_template(req["name"]),
            "cluster-settings": lambda: self.update_cluster_settings(
                req["body"]),
            "put-percolator": lambda: isvc.put_percolator(
                req["index"], req["id"], req["body"]),
            "delete-percolator": lambda: isvc.delete_percolator(
                req["index"], req["id"]),
            "put-repository": lambda: self.snapshots_service.put_repository(
                req["name"], req["body"]),
            "delete-repository": lambda:
                self.snapshots_service.delete_repository(req["name"]),
            "create-snapshot": lambda:
                self.snapshots_service._create_on_master(
                    req["repo"], req["snapshot"], req["body"]),
            "delete-snapshot": lambda:
                self.snapshots_service.delete_snapshot(req["repo"],
                                                       req["snapshot"]),
            "restore-snapshot": lambda:
                self.snapshots_service._restore_on_master(
                    req["repo"], req["snapshot"], req["body"]),
            "cluster-reroute": lambda: self._reroute_on_master(
                req.get("commands") or []),
            "put-script": lambda: self._put_script_on_master(
                req["lang"], req["id"], req["source"]),
            "delete-script": lambda: self._delete_script_on_master(
                req["lang"], req["id"]),
            "import-dangling": lambda: self._import_dangling_on_master(
                req["name"], req["meta"]),
        }
        fn = dispatch.get(action)
        if fn is None:
            raise ValueError(f"unknown master action [{action}]")
        out = fn()
        if isinstance(out, dict):        # e.g. put-script's created flag
            return {"acknowledged": True, **out}
        return {"acknowledged": True}

    # ---- dangling-indices import (core/gateway/DanglingIndicesState.java) --

    def _import_dangling(self, name: str, meta_dict: dict) -> None:
        """Offer an orphaned on-disk index to the elected master (local
        when we are it); the master re-imports the metadata and allocates
        — unless a tombstone or a racing re-create made the offer stale."""
        self.indices_service._master_op(
            "import-dangling", {"name": name, "meta": meta_dict},
            lambda: self._import_dangling_on_master(name, meta_dict))

    def _import_dangling_on_master(self, name: str,
                                   meta_dict: dict) -> None:
        def update(state: ClusterState) -> ClusterState:
            if name in state.indices:
                return state                     # re-created meanwhile
            tombs = state.customs.get("index_tombstones", [])
            uuid_ = meta_dict.get("uuid", "")
            for t in tombs:
                if t.get("index") == name or \
                        (uuid_ and t.get("uuid") == uuid_):
                    return state                 # deleted: stays dead
            meta = IndexMetadata.from_state_dict(name, meta_dict)
            return self.allocation.reroute(
                state.with_(
                    indices={**state.indices, name: meta},
                    routing_table=state.routing_table.add_index(meta)),
                f"dangling index imported [{name}]")
        self.cluster_service.submit_and_wait(
            f"import-dangling [{name}]", update)

    # ---- cluster-level metadata (master ops) -------------------------------

    def put_template(self, name: str, body: dict) -> None:
        self.indices_service._master_op(
            "put-template", {"name": name, "body": body},
            lambda: self.cluster_service.submit_and_wait(
                f"put-template [{name}]",
                lambda st: st.with_(templates={**st.templates, name: body})))

    def delete_template(self, name: str) -> None:
        self.indices_service._master_op(
            "delete-template", {"name": name},
            lambda: self.cluster_service.submit_and_wait(
                f"delete-template [{name}]",
                lambda st: st.with_(templates={
                    k: v for k, v in st.templates.items() if k != name})))

    def update_cluster_settings(self, body: dict) -> None:
        """PUT /_cluster/settings — persistent + transient scopes stored in
        cluster state (DynamicSettings / NodeSettingsService analog)."""
        def local():
            def update(st: ClusterState) -> ClusterState:
                persistent = {**st.persistent_settings,
                              **Settings(body.get("persistent",
                                                  {})).as_dict()}
                transient = {**st.transient_settings,
                             **Settings(body.get("transient", {})).as_dict()}
                return st.with_(persistent_settings=persistent,
                                transient_settings=transient)
            self.cluster_service.submit_and_wait("cluster-settings", update)
        self.indices_service._master_op("cluster-settings", {"body": body},
                                        local)

    # ---- ShardStateAction (core/cluster/action/shard/ShardStateAction.java)

    def _on_shard_started(self, shard) -> None:
        """Report to the master; locally if we are it."""
        state = self.cluster_service.state()
        if state.master_node_id == self.node_id:
            self.cluster_service.submit_state_update(
                f"shard-started [{shard.index}][{shard.shard}]",
                lambda st: self.allocation.apply_started_shards(st, [shard]),
                priority=URGENT)
            return
        master = state.master_node
        if master is None:
            self.indices_service.unreport(shard.allocation_id)
            return
        fut = self.transport_service.send_request(
            master, self.SHARD_STARTED_ACTION, {"shard": shard.to_dict()},
            timeout=10.0)
        fut.add_done_callback(
            lambda f: self._retry_shard_report(shard)
            if f.exception() is not None else None)

    def _retry_shard_report(self, shard) -> None:
        """A lost started-report must be re-sent even on a quiescent
        cluster (the reference resends on every applied state AND the
        master re-pings INITIALIZING shards)."""
        import threading
        self.indices_service.unreport(shard.allocation_id)
        t = threading.Timer(1.0, self._recheck_shards)
        t.daemon = True
        t.start()

    def _recheck_shards(self) -> None:
        if not self._started:
            return
        try:
            self.cluster_service.run_task(
                "recheck-shards",
                lambda: self.indices_service._cluster_changed(
                    self.cluster_service.state(),
                    self.cluster_service.state()))
        except RuntimeError:
            pass                                 # shutting down

    def _on_shard_failed(self, shard, details: str) -> None:
        state = self.cluster_service.state()
        if state.master_node_id == self.node_id:
            self.cluster_service.submit_state_update(
                f"shard-failed [{shard.index}][{shard.shard}]",
                lambda st: self.allocation.apply_failed_shards(
                    st, [(shard, details)]),
                priority=URGENT)
            return
        master = state.master_node
        if master is None:
            self._retry_shard_failed(shard, details)
            return
        fut = self.transport_service.send_request(
            master, self.SHARD_FAILED_ACTION,
            {"shard": shard.to_dict(), "details": details}, timeout=10.0)
        fut.add_done_callback(
            lambda f: self._retry_shard_failed(shard, details)
            if f.exception() is not None else None)

    def _retry_shard_failed(self, shard, details: str) -> None:
        """A failed-shard report lost to a dying/absent master MUST be
        re-sent: until some master applies it, the cluster state keeps
        advertising a copy that missed writes as active — reads served
        from it silently lose acked documents (a chaos-matrix find:
        replica fan-out failure racing a master kill)."""
        import threading
        t = threading.Timer(1.0, self._resend_shard_failed,
                            (shard, details))
        t.daemon = True
        t.start()

    def _resend_shard_failed(self, shard, details: str) -> None:
        if not self._started:
            return
        st = self.cluster_service.state()
        cur = [s for s in st.routing_table.shard_copies(shard.index,
                                                        shard.shard)
               if s.allocation_id == shard.allocation_id]
        if not cur or not cur[0].assigned:
            return                               # already applied
        self._on_shard_failed(shard, details)

    def _handle_shard_started(self, request: dict, source) -> dict:
        from elasticsearch_tpu.cluster.state import ShardRouting
        shard = ShardRouting.from_dict(request["shard"])
        self.cluster_service.submit_state_update(
            f"shard-started [{shard.index}][{shard.shard}] (remote)",
            lambda st: self.allocation.apply_started_shards(st, [shard]),
            priority=URGENT).result(10.0)
        return {}

    def _handle_shard_failed(self, request: dict, source) -> dict:
        from elasticsearch_tpu.cluster.state import ShardRouting
        shard = ShardRouting.from_dict(request["shard"])
        details = request.get("details", "")
        self.cluster_service.submit_state_update(
            f"shard-failed [{shard.index}][{shard.shard}] (remote)",
            lambda st: self.allocation.apply_failed_shards(
                st, [(shard, details)]),
            priority=URGENT).result(10.0)
        return {}

    # ---- task management (core/tasks/, TransportListTasksAction etc.) ------

    TASKS_LIST_ACTION = "cluster:monitor/tasks/lists[n]"
    TASK_CANCEL_ACTION = "cluster:admin/tasks/cancel"
    TASK_BAN_ACTION = "internal:admin/tasks/ban"

    def _handle_tasks_list(self, request: dict, source) -> dict:
        request = request or {}
        return {
            "name": self.node_name,
            "transport_address":
                str(self.transport_service.local_node.address),
            "tasks": self.task_manager.list_tasks(
                actions=request.get("actions"),
                parent_task_id=request.get("parent_task_id"),
                detailed=request.get("detailed", True))}

    def collect_tasks(self, actions: list[str] | None = None,
                      parent_task_id: str | None = None,
                      nodes: list[str] | None = None,
                      detailed: bool = True) -> dict:
        """GET /_tasks — every node's matching tasks, collected over the
        transport (TransportListTasksAction fan-out)."""
        per_node = self._fan_out_nodes(
            self.TASKS_LIST_ACTION,
            {"actions": actions, "parent_task_id": parent_task_id,
             "detailed": detailed})
        if nodes:
            wanted = set(nodes)
            per_node = {nid: doc for nid, doc in per_node.items()
                        if nid in wanted or doc.get("name") in wanted}
        return {"nodes": per_node}

    def cancel_task(self, task_id: str,
                    reason: str = "by user request") -> dict:
        """POST /_tasks/{id}/_cancel — routed to the task's OWNER node
        (the id's node part); the owner marks the task and its local
        descendants cancelled and broadcasts a ban on the id so children
        on every other node — current and future — cancel too."""
        owner, _, _ = str(task_id).rpartition(":")
        if owner == self.node_id or not owner:
            return self._cancel_local_task(task_id, reason)
        state = self.cluster_service.state()
        target = state.node(owner)
        if target is None:
            return {"found": False, "task_id": task_id}
        from elasticsearch_tpu.action.replication import unwrap_remote
        try:
            return self.transport_service.send_request(
                target, self.TASK_CANCEL_ACTION,
                {"task_id": task_id, "reason": reason},
                timeout=10.0).result(15.0)
        except Exception as e:               # noqa: BLE001 — unwrap
            raise unwrap_remote(e) from None

    def _cancel_local_task(self, task_id: str, reason: str) -> dict:
        tm = self.task_manager
        task = tm.get(task_id)
        if task is None:
            return {"found": False, "task_id": task_id}
        tm.cancel(task, reason)
        # ban the id cluster-wide; the flag makes unregister lift it
        task.ban_sent = True
        self._broadcast_task_ban(task.task_id, True, reason)
        return {"found": True, "task_id": task_id,
                "task": task.to_dict()}

    def _broadcast_task_ban(self, parent_task_id: str, ban: bool,
                            reason: str) -> None:
        """Fire-and-forget ban (or ban removal) to every other node —
        TaskManager.setBan propagation. Best-effort: a node that misses
        the ban still reaps the children when the parent node leaves."""
        state = self.cluster_service.state()
        for nid, n in state.nodes.items():
            if nid == self.node_id:
                continue
            try:
                self.transport_service.send_request(
                    n, self.TASK_BAN_ACTION,
                    {"parent": parent_task_id, "ban": ban,
                     "reason": reason}, timeout=5.0)
            except Exception:                # noqa: BLE001 — best effort
                continue

    def _handle_task_cancel(self, request: dict, source) -> dict:
        return self._cancel_local_task(
            request["task_id"], request.get("reason", "by user request"))

    def _handle_task_ban(self, request: dict, source) -> dict:
        if request.get("ban", True):
            n = self.task_manager.set_ban(
                request["parent"], request.get("reason", "parent banned"))
            return {"cancelled": n}
        self.task_manager.remove_ban(request["parent"])
        return {"cancelled": 0}

    def _reap_tasks_on_node_left(self, old, new) -> None:
        for nid in set(old.nodes) - set(new.nodes):
            self.task_manager.reap_node_left(nid)

    # ---- node-level monitoring (nodes stats / hot threads fan-out) ---------

    NODE_STATS_ACTION = "cluster:monitor/nodes/stats[n]"
    HOT_THREADS_ACTION = "cluster:monitor/nodes/hot_threads[n]"
    TRACE_COLLECT_ACTION = "cluster:monitor/nodes/trace[n]"

    def local_node_stats(self) -> dict:
        """This node's stats document (core/action/admin/cluster/node/stats
        — indices rollup, breakers, thread pools, process/os probes)."""
        from elasticsearch_tpu.monitor import os_stats, process_stats
        indices_total = {"docs": {"count": 0},
                         "store": {"size_in_bytes": 0,
                                   "throttle_time_in_millis": 0},
                         "segments": {"count": 0, "memory_in_bytes": 0},
                         "indexing": {"index_total": 0,
                                      "index_time_in_millis": 0}}
        # collective-plane admission rollup across this node's indices
        # (per-index detail lives in _stats; the flip to default-on is
        # observable here: served / fallback-by-reason), plus the
        # plane breaker (state, trip count, consecutive errors, last
        # error, probes) and which indices are plane-degraded —
        # the degraded-mode-serving dashboard
        from elasticsearch_tpu.search import jit_exec as _jx_breaker
        plane_total: dict = {"served": 0, "fallback": {},
                             "data_layer": {},
                             "breaker": _jx_breaker.plane_breaker.stats(),
                             "degraded_indices": sorted(
                                 name for name, svc in
                                 self.indices_service.indices.items()
                                 if svc.plane_stats.get("degraded"))}
        # percolate rollup: ops/time/registered queries summed across this
        # node's indices plus the registry program-cache counters (the
        # compiled-percolation analog of the collective_plane rollup)
        perc_total: dict = {"total": 0, "time_in_millis": 0, "current": 0,
                            "queries": 0}
        for svc in list(self.indices_service.indices.values()):
            plane_total["served"] += svc.plane_stats["served"]
            for reason, n in svc.plane_stats["fallback"].items():
                plane_total["fallback"][reason] = \
                    plane_total["fallback"].get(reason, 0) + n
            for k, v in svc.plane_stats.get("data_layer", {}).items():
                plane_total["data_layer"][k] = \
                    plane_total["data_layer"].get(k, 0) + v
            ps_idx = svc._percolate_stats()
            perc_total["total"] += ps_idx["total"]
            perc_total["time_in_millis"] += ps_idx["time_in_millis"]
            perc_total["queries"] += ps_idx["queries"]
            s = svc.stats()
            indices_total["docs"]["count"] += s["docs"]["count"]
            indices_total["store"]["size_in_bytes"] += \
                s.get("store", {}).get("size_in_bytes", 0)
            indices_total["segments"]["count"] += s["segments"]["count"]
            indices_total["segments"]["memory_in_bytes"] += \
                s["segments"]["memory_in_bytes"]
            indices_total["indexing"]["index_total"] += \
                s["indexing"]["index_total"]
            indices_total["indexing"]["index_time_in_millis"] += \
                s["indexing"]["index_time_in_millis"]
        pools = self.thread_pool.stats()
        recovery = getattr(self, "recovery_service", None)
        indices_total["request_cache"] = \
            self.search_actions.request_cache.stats_dict()
        indices_total["collective_plane"] = plane_total
        indices_total["percolate"] = perc_total
        # compiled-path counters: per-segment program cache plus the
        # plane's shape-keyed program layer (mesh_program_{hits,misses})
        # and fallback reasons — the trace/compile budget, observable.
        # `node_local` is THIS node's attributed slice of the shared
        # module-level rollup (in-process nodes share one device, so the
        # top-level numbers are process-wide; the slice is what isolates
        # one node's activity in multi-node stats)
        from elasticsearch_tpu.search import jit_exec as _jit_exec
        indices_total["jit"] = {
            **_jit_exec.cache_stats(),
            "node_local": _jit_exec.cache_stats(self.node_id)}
        ps = process_stats()
        osx = os_stats()
        heap = ps["mem"]["resident_in_bytes"]
        total_mem = osx.get("mem", {}).get("total_in_bytes", heap or 1)
        from elasticsearch_tpu.observability import costs as _costs
        from elasticsearch_tpu.observability import flightrec as _flight
        from elasticsearch_tpu.observability import histograms as _hist
        from elasticsearch_tpu.observability import slo as _slo
        from elasticsearch_tpu.observability import timeseries as _ts
        from elasticsearch_tpu.observability import tracing as _tracing
        # every stats read advances the telemetry ring (throttled), so
        # the windowed sections below always reflect this scrape
        self.telemetry_tick()
        rates_doc = _ts.rates(self.node_id)
        rates_doc["slo_burn"] = _slo.windowed_burn(self.node_id,
                                                   rates_doc)
        return {
            "name": self.node_name,
            "timestamp": int(time.time() * 1000),
            "indices": indices_total,
            "breakers": self.breaker_service.stats(),
            # the device-memory ledger: every HBM reservation on this
            # node keyed (index, engine, component), reconciling with
            # breakers.fielddata.estimated_size_in_bytes
            "device_memory": self.breaker_service.device_ledger.snapshot(
                resolve_index=self.resolve_engine_index),
            # rolling-window rates + windowed percentiles (1m/5m/15m)
            # from the telemetry ring, plus per-window SLO burn rates
            "rates": rates_doc,
            # SLO burn accounting: objective, per-lane good/bad totals,
            # cumulative burn rate
            "slo": _slo.stats(self.node_id),
            "thread_pool": pools,
            "tasks": self.task_manager.stats(),
            # adaptive replica selection: per-target-node C3 ranks/EWMAs
            # this coordinator observed, plus the hedged-request counters
            # (hedges_launched == hedges_won + hedges_cancelled +
            # in_flight at every instant)
            "adaptive_selection":
                self.search_actions.replica_stats.stats_dict(),
            # continuous-batching scheduler: queue depths, batches
            # launched/in-flight/drained, shed counts by reason, and the
            # sample-time reconciliation verdict (submitted == queued +
            # in_flight + delivered + declined + shed)
            "scheduler": self.search_actions.scheduler.stats(),
            # dispatch watchdog: live in-flight device waits (with the
            # oldest wait's age — the stall liveness gauge), the
            # escalation tallies (stalls/abandoned/quarantines/
            # probe_reopens), and the envelope config
            "watchdog": self.search_actions.watchdog.stats(),
            # program cost observatory: per-lane rollups over the
            # resident compiled programs (XLA static cost + live
            # dispatch stats, predicted vs measured) and the top
            # programs by device time; table accounting reconciles
            # (inserted == resident + evicted + dropped)
            "programs": _costs.stats_doc(self.node_id),
            # anomaly flight recorder occupancy (full ring via
            # GET /_nodes/diagnostics)
            "flight_recorder": _flight.stats(self.node_id),
            # per-lane latency distributions (fixed-bucket histograms,
            # always on) + this node's span-store accounting
            "latency": _hist.summaries(self.node_id),
            "tracing": {**_tracing.store_stats(self.node_id),
                        # the always-on span ring (process-wide)
                        "ring": _tracing.ring_stats()},
            # the in-flight book: how long the host left the device
            # with no launch in flight (``starved_pct``), process-wide
            # and cumulative — two reads give a window
            "device": {**_tracing.book_stats(),
                       "mesh": self.serving_mesh_doc()},
            "process": ps,
            "os": osx,
            # process-level memory reported under the reference's jvm
            # section name (there is no JVM; RSS plays the heap role)
            "jvm": {"timestamp": ps["timestamp"],
                    "uptime_in_millis": ps["uptime_in_millis"],
                    "mem": {"heap_used_in_bytes": heap,
                            "heap_used_percent":
                                int(100 * heap / max(total_mem, 1)),
                            "heap_max_in_bytes": total_mem},
                    "threads": {"count": _threading.active_count(),
                                "peak_count": _threading.active_count()},
                    "gc": {"collectors": {}},
                    "buffer_pools": {
                        "direct": {"count": 0, "used_in_bytes": 0,
                                   "total_capacity_in_bytes": 0},
                        "mapped": {"count": 0, "used_in_bytes": 0,
                                   "total_capacity_in_bytes": 0}}},
            "transport": {"server_open": 0, "rx_count": 0,
                          "rx_size_in_bytes": 0, "tx_count": 0,
                          "tx_size_in_bytes": 0},
            "fs": self._fs_stats(ps["timestamp"]),
            "http": {"current_open": 0, "total_opened": 0},
            "recovery": dict(recovery.stats) if recovery else {},
        }

    def _fs_stats(self, ts: int) -> dict:
        import shutil as _sh
        try:
            du = _sh.disk_usage(str(self.data_path))
            entry = {"path": str(self.data_path), "type": "local",
                     "total_in_bytes": du.total,
                     "free_in_bytes": du.free,
                     "available_in_bytes": du.free}
        except OSError:
            entry = {"path": str(self.data_path), "type": "local",
                     "total_in_bytes": 0,
                     "free_in_bytes": 0, "available_in_bytes": 0}
        total = {k: v for k, v in entry.items()
                 if k not in ("path", "type")}
        return {"timestamp": ts, "total": total, "data": [entry]}

    @staticmethod
    def _parse_buffer_size(raw) -> int:
        """'10%' of total memory, or an absolute byte size ('512mb')."""
        s = str(raw).strip().lower()
        if s.endswith("%"):
            try:
                import os as _os
                total = _os.sysconf("SC_PHYS_PAGES") * \
                    _os.sysconf("SC_PAGE_SIZE")
            except (OSError, ValueError):
                total = 1 << 32
            return int(total * float(s[:-1]) / 100.0)
        units = {"kb": 1 << 10, "mb": 1 << 20, "gb": 1 << 30, "b": 1}
        for suffix, mult in units.items():
            if s.endswith(suffix):
                return int(float(s[: -len(suffix)]) * mult)
        return int(float(s))

    def _schedule_imc(self) -> None:
        t = _threading.Timer(
            self.settings.get_as_float(
                "indices.memory.interval_s", 5.0), self._imc_tick)
        t.daemon = True
        self._imc_timer = t
        t.start()

    def _imc_tick(self) -> None:
        try:
            self.indexing_memory_check()
        except Exception:                # noqa: BLE001 — keep governing
            pass
        if self._started:
            self._schedule_imc()

    def indexing_memory_check(self) -> int:
        """One governor pass: refresh the largest write buffers until the
        node-wide total fits the budget. → buffers refreshed."""
        sized = []
        for name, svc in list(self.indices_service.indices.items()):
            for sid, engine in list(svc.engines.items()):
                try:
                    sized.append((engine.buffer_memory_bytes(), engine))
                except Exception:        # noqa: BLE001 — engine closing
                    continue
        total = sum(b for b, _ in sized)
        refreshed = 0
        if total <= self._index_buffer_budget:
            return 0
        for nbytes, engine in sorted(sized, key=lambda x: -x[0]):
            if total <= self._index_buffer_budget or nbytes == 0:
                break
            try:
                engine.refresh()
                refreshed += 1
                total -= nbytes
            except Exception:            # noqa: BLE001 — engine closing
                continue
        return refreshed

    def _schedule_ttl_sweep(self) -> None:
        t = _threading.Timer(self._ttl_interval, self._ttl_tick)
        t.daemon = True
        self._ttl_timer = t
        t.start()

    def _ttl_tick(self) -> None:
        try:
            self.ttl_sweep_once()
        except Exception:                # noqa: BLE001 — keep sweeping
            pass
        if self._started:
            self._schedule_ttl_sweep()

    def ttl_sweep_once(self) -> int:
        """One TTL purge pass (IndicesTTLService.PurgerThread): find
        expired docs per local shard, delete them through the replicated
        path (routing-aware via the doc's stored _routing)."""
        now_ms = int(time.time() * 1000)
        purged = 0
        state = self.cluster_service.state()
        for name, svc in list(self.indices_service.indices.items()):
            # only primaries sweep (IndicesTTLService purges on primary
            # shards; replicas receive the replicated deletes)
            primaries = {s.shard for s in
                         state.routing_table.index_shards(name)
                         if s.primary and s.node_id == self.node_id}
            for sid, engine in list(svc.engines.items()):
                if sid not in primaries:
                    continue
                for did in engine.expired_docs(now_ms):
                    try:
                        got = engine.get(did)
                        routing = (got.meta or {}).get("_routing")
                        self.document_actions.delete_doc(name, did,
                                                         routing=routing)
                        purged += 1
                    except Exception:    # noqa: BLE001 — racing writes
                        continue
        return purged

    def _handle_node_stats(self, request: dict, source) -> dict:
        return self.local_node_stats()

    def _handle_hot_threads(self, request: dict, source) -> dict:
        from elasticsearch_tpu.monitor import hot_threads
        return {"text": hot_threads(
            snapshots=int(request.get("snapshots", 10)),
            interval=float(request.get("interval", 0.05)),
            threads=int(request.get("threads", 3)))}

    def _fan_out_nodes(self, action: str, request: dict) -> dict:
        """Collect one payload per cluster node (TransportNodesAction)."""
        state = self.cluster_service.state()
        out = {}
        futures = []
        for nid, n in state.nodes.items():
            if nid == self.node_id:
                continue
            futures.append((nid, self.transport_service.send_request(
                n, action, request, timeout=15.0)))
        handler = {self.NODE_STATS_ACTION: self._handle_node_stats,
                   self.HOT_THREADS_ACTION: self._handle_hot_threads,
                   self.TASKS_LIST_ACTION: self._handle_tasks_list,
                   self.TRACE_COLLECT_ACTION:
                       self._handle_trace_collect}[action]
        out[self.node_id] = handler(request, None)
        for nid, fut in futures:
            try:
                out[nid] = fut.result(20.0)
            except Exception:                    # noqa: BLE001 — node gone
                continue
        return out

    def collect_nodes_stats(self) -> dict:
        return {"cluster_name": self.cluster_service.state().cluster_name,
                "nodes": self._fan_out_nodes(self.NODE_STATS_ACTION, {})}

    # ---- span tracing (observability/tracing.py) ---------------------------

    def _handle_trace_collect(self, request: dict, source) -> dict:
        """One node's span records — for one trace id, or everything in
        the store (the Chrome-trace dump)."""
        from elasticsearch_tpu.observability import tracing
        request = request or {}
        trace_id = request.get("trace_id")
        spans = tracing.spans_for(self.node_id, trace_id) if trace_id \
            else tracing.all_spans(self.node_id)
        from elasticsearch_tpu.observability import timeseries
        return {"name": self.node_name, "spans": spans,
                "stats": tracing.store_stats(self.node_id),
                # the telemetry ring's samples ride along so the Chrome
                # export can draw per-node counter tracks (ledger bytes,
                # lane counts) under the span timeline
                "counters": timeseries.ring_samples(self.node_id)}

    def collect_trace(self, trace_id: str) -> dict:
        """GET /_tasks/{id}/trace — gather one trace's spans from every
        node and reassemble the cross-node tree under the coordinating
        task id (span parent links survive the wire, so remote shard
        subtrees nest under the coordinator's fan-out spans)."""
        from elasticsearch_tpu.observability import tracing
        per_node = self._fan_out_nodes(self.TRACE_COLLECT_ACTION,
                                       {"trace_id": trace_id})
        spans = [s for doc in per_node.values() for s in doc["spans"]]
        return {
            "trace_id": trace_id,
            "span_count": len(spans),
            "nodes": sorted({s["node"] for s in spans}),
            "open_spans": sum(doc["stats"]["open_spans"]
                              for doc in per_node.values()),
            "tree": tracing.build_tree(spans),
        }

    def collect_chrome_trace(self, trace_id: str | None = None) -> dict:
        """GET /_nodes/trace — every node's stored spans (optionally one
        trace) as a Chrome Trace Event Format document for offline
        viewing in chrome://tracing / Perfetto."""
        from elasticsearch_tpu.observability import chrome
        self.telemetry_tick()            # the export's final sample
        per_node = self._fan_out_nodes(
            self.TRACE_COLLECT_ACTION,
            {"trace_id": trace_id} if trace_id else {})
        spans = [s for doc in per_node.values() for s in doc["spans"]]
        spans.sort(key=lambda s: s["start_us"])
        counters = {nid: doc.get("counters") or []
                    for nid, doc in per_node.items()}
        return chrome.chrome_trace(spans, counters=counters)

    # ---- live telemetry plane (observability/{ledger,timeseries}) ---------

    def resolve_engine_index(self, engine_uuid: str) -> str | None:
        """engine uuid → index name, for ledger rows whose charge site
        didn't know the index (the block cache keys by engine only)."""
        for name, svc in self.indices_service.indices.items():
            for engine in svc.engines.values():
                if engine.engine_uuid == engine_uuid:
                    return name
        return None

    def telemetry_tick(self, force: bool = False) -> bool:
        """Snapshot this node's cumulative counters into the timeseries
        ring (scrape-driven and throttled: search hot paths never pay
        for windowing). Hedge counters ride as extra series next to the
        lane/jit/slo/ledger sample."""
        from elasticsearch_tpu.observability import timeseries
        extra = {}
        try:
            for k, v in self.search_actions.replica_stats.hedge_stats() \
                    .items():
                if isinstance(v, (int, float)):
                    extra[f"hedge.{k}"] = v
        except Exception:                # noqa: BLE001 — pre-start tick
            pass
        return timeseries.tick(
            self.node_id, extra=extra,
            ledger=self.breaker_service.device_ledger, force=force)

    def collect_diagnostics(self, top: int = 25) -> dict:
        """GET /_nodes/diagnostics — the anomaly flight recorder's ring
        plus every book an operator needs next to it to diagnose a
        blown SLO after the fact, as ONE bundle: the program cost table
        (top programs + per-lane rollups), the device-memory ledger,
        windowed rates + SLO burn, scheduler depths, dispatch-watchdog
        stall state, and breaker states (plane + byte breakers)."""
        from elasticsearch_tpu.observability import costs as _costs
        from elasticsearch_tpu.observability import flightrec as _flight
        from elasticsearch_tpu.observability import slo as _slo
        from elasticsearch_tpu.observability import timeseries as _ts
        from elasticsearch_tpu.search import jit_exec as _jit_exec
        self.telemetry_tick()
        rates_doc = _ts.rates(self.node_id)
        rates_doc["slo_burn"] = _slo.windowed_burn(self.node_id,
                                                   rates_doc)
        return {
            "name": self.node_name,
            "timestamp": int(time.time() * 1000),
            "flight_recorder": {
                **_flight.stats(self.node_id),
                "events": _flight.events(self.node_id),
            },
            "programs": _costs.stats_doc(self.node_id, top=top),
            "device_memory": self.breaker_service.device_ledger.snapshot(
                resolve_index=self.resolve_engine_index),
            "rates": rates_doc,
            "slo": _slo.stats(self.node_id),
            "scheduler": self.search_actions.scheduler.stats(),
            # the hang half of the fault model next to the raise half
            # (breakers below): stalls, abandoned waits, quarantine
            # state, and the oldest in-flight wait's age
            "watchdog": self.search_actions.watchdog.stats(),
            "breakers": {
                "plane": _jit_exec.plane_breaker.stats(),
                "bytes": self.breaker_service.stats(),
            },
        }

    def collect_hot_threads(self, **params) -> str:
        per_node = self._fan_out_nodes(self.HOT_THREADS_ACTION, params)
        return "\n".join(f"::: node [{nid[:8]}]\n{p['text']}"
                         for nid, p in per_node.items())

    @property
    def is_master(self) -> bool:
        return self.cluster_service.state().master_node_id == self.node_id

    def _persist_state(self, old: ClusterState, new: ClusterState) -> None:
        new.persist(self.data_path / "_state")

    def _schedule_delayed_reroute(self, old, new) -> None:
        """RoutingService.scheduleDelayedReroute analog: when NODE_LEFT
        shards are waiting out their delayed-allocation window, arrange a
        reroute at expiry (only the master reroutes)."""
        import threading
        if new.master_node_id != self.node_id:
            return
        remaining = self.allocation.next_delayed_reroute_millis(new)
        if remaining is None:
            return
        if self._delayed_reroute_timer is not None and \
                self._delayed_reroute_timer.is_alive():
            return
        t = threading.Timer(remaining / 1000.0 + 0.05, self._delayed_reroute)
        t.daemon = True
        t.start()
        self._delayed_reroute_timer = t

    def _delayed_reroute(self) -> None:
        if not self._started:
            return
        try:
            self.cluster_service.submit_state_update(
                "delayed reroute",
                lambda st: self.allocation.reroute(st, "delay expired"),
                priority=URGENT)
        except RuntimeError:
            pass                                 # cluster service closed

    def wait_for_health(self, status: str | None = "green",
                        timeout: float = 10.0,
                        wait_for_nodes: str | int | None = None) -> dict:
        """Health wait (wait_for_status / wait_for_nodes params of the
        health API). `wait_for_nodes` accepts N, '>=N', '<=N', '>N', '<N';
        status=None waits only on the node predicate."""
        want = {"green": ("green",), "yellow": ("green", "yellow"),
                None: ("green", "yellow", "red")}[status]
        deadline = time.monotonic() + timeout
        while True:
            h = self.cluster_service.state().health(
                len(self.cluster_service.pending_tasks()))
            nodes_ok = _nodes_predicate(wait_for_nodes, h["number_of_nodes"])
            if h["status"] in want and nodes_ok and \
                    h["number_of_pending_tasks"] == 0:
                return h
            if time.monotonic() > deadline:
                h["timed_out"] = True
                return h
            time.sleep(0.01)

    def _install_serving_mesh(self):
        """``search.mesh: "<dp>x<shard>"`` — the device mesh this node
        serves over: the collective plane packs an index's shards over
        its ``shard`` axis (one shard a chip where they are as many) and
        the impact / knn mesh lanes shard over it
        (``jit_exec.set_serving_mesh``). Validated against the devices
        JAX sees by ``make_mesh``, whose rejection lists the valid
        geometries: a bad geometry stops the node here, not the first
        search. A geometry of fewer devices than the host has takes the
        leading ones. Absent = one device, every path as it is without
        a mesh. → the Mesh, or None."""
        raw = self.settings.get("search.mesh")
        if raw is None or str(raw).strip() == "":
            return None
        from elasticsearch_tpu.common import IllegalArgumentError
        from elasticsearch_tpu.parallel.mesh import make_mesh
        from elasticsearch_tpu.search import jit_exec
        parts = str(raw).lower().split("x")
        if len(parts) != 2 or not all(p.strip().isdigit() for p in parts):
            raise IllegalArgumentError(
                f"search.mesh [{raw}] is not of the form <dp>x<shard>, "
                f"as in 1x4")
        dp, shard = int(parts[0]), int(parts[1])
        import jax
        devices = jax.devices()
        if 0 < dp * shard < len(devices):
            # a mesh smaller than the host takes its leading devices
            devices = devices[:dp * shard]
        mesh = make_mesh(dp=dp, shard=shard, devices=devices)
        jit_exec.set_serving_mesh(mesh)
        return mesh

    def serving_mesh_doc(self) -> dict:
        """``_nodes/stats`` ``device.mesh``: the geometry searches are
        served over and the devices it spans."""
        mesh = self.serving_mesh
        if mesh is None:
            return {"dp": 1, "shard": 1, "devices": 1, "setting": None}
        return {"dp": int(mesh.shape["dp"]),
                "shard": int(mesh.shape["shard"]),
                "devices": int(mesh.devices.size),
                "device_ids": [int(d.id) for d in mesh.devices.flat],
                "setting": str(self.settings.get("search.mesh"))}

    def _remove_serving_mesh(self) -> None:
        from elasticsearch_tpu.search import jit_exec
        mesh, self.serving_mesh = self.serving_mesh, None
        if mesh is not None and jit_exec.serving_mesh() is mesh:
            jit_exec.set_serving_mesh(None)

    def close(self) -> None:
        """Graceful shutdown: leave the cluster, then stop services."""
        self._remove_serving_mesh()
        if self._started:
            self._started = False
            self.plugins_service.apply_node_stop(self)
            if self._delayed_reroute_timer is not None:
                self._delayed_reroute_timer.cancel()
            if self._ttl_timer is not None:
                self._ttl_timer.cancel()
            if getattr(self, "_imc_timer", None) is not None:
                self._imc_timer.cancel()
            if getattr(self, "resource_watcher", None):
                self.resource_watcher.stop()
            if getattr(self, "cluster_info_service", None):
                self.cluster_info_service.stop()
            self.search_actions.close()
            self.discovery.stop()
            self.indices_service.close()
            self.cluster_service.close()
            self.transport_service.close()
            self.thread_pool.shutdown()

    def kill(self) -> None:
        """Abrupt death — no leave notification, no flush ordering; the
        cluster must detect the loss via fault detection (test disruption
        helper, mirrors InternalTestCluster restartNode(KILL))."""
        self._remove_serving_mesh()
        if self._started:
            self._started = False
            if self._delayed_reroute_timer is not None:
                self._delayed_reroute_timer.cancel()
            if getattr(self, "cluster_info_service", None):
                self.cluster_info_service.stop()
            self.transport_service.close()
            self.discovery.master_fd.stop()
            self.discovery.nodes_fd.stop()
            self.discovery._running = False
            self.cluster_service.close()
            self.indices_service.close()
            self.thread_pool.shutdown()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()

    # ---- document action layer (core/action/{index,get,delete,update}) ----

    def index_doc(self, index: str, doc_id: str | None, source: dict,
                  routing: str | None = None, version: int | None = None,
                  op_type: str = "index", refresh: bool = False,
                  version_type: str = "internal",
                  meta: dict | None = None) -> dict:
        return self.document_actions.index_doc(
            index, doc_id, source, routing=routing, version=version,
            op_type=op_type, refresh=refresh, version_type=version_type,
            meta=meta)

    def get_doc(self, index: str, doc_id: str,
                routing: str | None = None, realtime: bool = True,
                refresh: bool = False) -> dict:
        return self.document_actions.get_doc(index, doc_id, routing=routing,
                                             realtime=realtime,
                                             refresh=refresh)

    def delete_doc(self, index: str, doc_id: str,
                   routing: str | None = None, version: int | None = None,
                   refresh: bool = False,
                   version_type: str = "internal") -> dict:
        return self.document_actions.delete_doc(
            index, doc_id, routing=routing, version=version, refresh=refresh,
            version_type=version_type)

    def update_doc(self, index: str, doc_id: str, body: dict,
                   routing: str | None = None, refresh: bool = False,
                   version: int | None = None,
                   meta: dict | None = None) -> dict:
        return self.document_actions.update_doc(
            index, doc_id, body, routing=routing, refresh=refresh,
            version=version, meta=meta)

    def mget(self, body: dict, default_index: str | None = None,
             realtime: bool = True, refresh: bool = False) -> dict:
        return self.document_actions.mget(body, default_index,
                                          realtime=realtime,
                                          refresh=refresh)

    def bulk(self, operations: list[tuple[str, dict, dict | None]],
             refresh: bool = False) -> dict:
        """operations: (action, metadata, source) triples, pre-parsed from
        NDJSON by the REST layer or built by the client."""
        return self.document_actions.bulk(operations, refresh=refresh)

    # ---- search entry ------------------------------------------------------

    def search(self, index: str, body: dict | None = None,
               scroll: str | None = None,
               search_type: str | None = None,
               routing: str | None = None,
               preference: str | None = None) -> dict:
        return self.search_actions.search(index, body, scroll=scroll,
                                          search_type=search_type,
                                          routing=routing,
                                          preference=preference)

    def count(self, index: str, body: dict | None = None,
              routing: str | None = None,
              preference: str | None = None) -> dict:
        return self.search_actions.count(index, body, routing=routing,
                                         preference=preference)


def _nodes_predicate(expr, actual: int) -> bool:
    if expr is None:
        return True
    s = str(expr)
    for op, fn in ((">=", lambda a, b: a >= b), ("<=", lambda a, b: a <= b),
                   (">", lambda a, b: a > b), ("<", lambda a, b: a < b)):
        if s.startswith(op):
            return fn(actual, int(s[len(op):]))
    return actual == int(s)


def _deep_merge(base: dict, patch: dict) -> dict:
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            base[k] = _deep_merge(dict(base[k]), v)
        else:
            base[k] = v
    return base


def _apply_update_script(source: dict, script,
                         meta: dict | None = None
                         ) -> tuple[dict, str, dict]:
    """Run an update script against the document (UpdateHelper.prepare):
    the script sees `ctx` with a mutable `_source` plus `op`/`_ttl`/
    `_timestamp`/`_id` and `params`; → (new source, op, meta_updates)
    where op is "index" (reindex), "none" (noop) or "delete" (remove the
    doc) and meta_updates carries any _ttl/_timestamp the script set.
    Interpreted by GroovyLite (scriptlang.py), the lang-groovy analog —
    conditionals, loops and collection mutation all work."""
    from elasticsearch_tpu.search.script_engines import resolve_engine
    lang = None
    if isinstance(script, dict):
        src = script.get("source", script.get("inline", ""))
        params = script.get("params", {})
        lang = script.get("lang")
    else:
        src, params = str(script), {}
    compile_fn = resolve_engine(lang)
    ctx = {"_source": source, "op": "index", **(meta or {})}
    before = {k: ctx.get(k) for k in ("_ttl", "_timestamp")}
    compile_fn(src).run({"ctx": ctx, "params": params})
    op = ctx.get("op", "index")
    if op not in ("index", "none", "noop", "delete"):
        raise ValueError(f"invalid ctx.op [{op}]")
    # scripts may restamp ttl/timestamp (UpdateHelper reads ctx._ttl /
    # ctx._timestamp after the script runs)
    meta_updates = {k: ctx[k] for k in ("_ttl", "_timestamp")
                    if ctx.get(k) is not None and ctx.get(k) != before[k]}
    return ctx["_source"], "none" if op == "noop" else op, meta_updates
