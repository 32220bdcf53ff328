"""TransportService — request/response RPC over named actions.

Reference: core/transport/TransportService.java — handler registry
(`registerRequestHandler`), `sendRequest` with timeout handling
(TimeoutHandler), response-handler table keyed by request id, tracer hook
(`transport.tracer.include`), and the local-node shortcut. Payloads always
round-trip through the wire codec (stream.py) even in-process, so the
LocalTransport test seam exercises the same serialization as TCP —
mirroring how LocalTransport.java still serializes messages.
"""

from __future__ import annotations

import threading
import time
import uuid
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable

from elasticsearch_tpu.transport.stream import (
    CURRENT_VERSION, StreamInput, StreamOutput)

#: fault-injection verdict: swallow the message entirely. Shared with the
#: transport-level seams (local.py re-exports it; tcp.py compares to the
#: same literal) so one scheme vocabulary covers both layers.
DROP = "drop"
#: fault-injection verdict constructors — a rule may also return
#: ("duplicate", n) to deliver 1+n copies, or ("reorder", jitter_s) to
#: hold the message and release it after the jitter (later messages pass
#: it, which is what reordering IS on an ordered transport).
DUPLICATE = "duplicate"
REORDER = "reorder"

#: inbound request ids remembered per node for at-most-once delivery; a
#: duplicate arrives within the fault seam's reorder jitter, far inside
#: this many later requests
DELIVERED_WINDOW = 16384


class TransportException(Exception):
    pass


class ActionNotFoundError(TransportException):
    pass


class ConnectTransportError(TransportException):
    pass


class NodeDisconnectedError(ConnectTransportError):
    pass


class ReceiveTimeoutError(TransportException):
    pass


class RemoteTransportError(TransportException):
    """Failure raised by the remote handler; carries the remote error type."""

    def __init__(self, node_name: str, action: str, error_type: str,
                 reason: str):
        super().__init__(f"[{node_name}][{action}] {error_type}: {reason}")
        self.node_name = node_name
        self.action = action
        self.error_type = error_type
        self.reason = reason


@dataclass(frozen=True)
class TransportAddress:
    host: str
    port: int

    def __str__(self) -> str:
        return f"{self.host}:{self.port}"


@dataclass(frozen=True)
class DiscoveryNode:
    """Reference: core/cluster/node/DiscoveryNode.java — id, name, address,
    attributes (data/master roles), wire version, build hash (1_000_100+,
    the Build.java analog surfaced in nodes info)."""
    node_id: str
    name: str
    address: TransportAddress
    attributes: tuple = ()
    version: int = CURRENT_VERSION
    build: str = ""

    @property
    def master_eligible(self) -> bool:
        return dict(self.attributes).get("master", "true") == "true"

    @property
    def data_node(self) -> bool:
        return dict(self.attributes).get("data", "true") == "true"

    def to_wire(self, out: StreamOutput) -> None:
        out.write_string(self.node_id)
        out.write_string(self.name)
        out.write_string(self.address.host)
        out.write_int(self.address.port)
        out.write_value(dict(self.attributes))
        out.write_vint(self.version)
        # gated field (StreamInput.java:58 pattern): both sides agreed on
        # min(local, remote) for this stream, so a 1_000_099 peer neither
        # writes nor expects the build hash
        if out.version >= 1_000_100:
            out.write_string(self.build)

    @staticmethod
    def from_wire(inp: StreamInput) -> "DiscoveryNode":
        node_id = inp.read_string()
        name = inp.read_string()
        address = TransportAddress(inp.read_string(), inp.read_int())
        attributes = tuple(sorted(inp.read_value().items()))
        version = inp.read_vint()
        build = inp.read_string() if inp.version >= 1_000_100 else ""
        return DiscoveryNode(node_id=node_id, name=name, address=address,
                             attributes=attributes, version=version,
                             build=build)


class TransportChannel:
    """Reply channel handed to request handlers (TransportChannel.java)."""

    def __init__(self, service: "TransportService", source: DiscoveryNode,
                 request_id: int, action: str):
        self._service = service
        self.source_node = source
        self.request_id = request_id
        self.action = action
        self._done = False
        # the Task registered for this request (TaskManager wiring);
        # unregistered when the reply goes out — the task's lifetime IS
        # the request's lifetime
        self.task = None

    def send_response(self, response: dict | None) -> None:
        if self._done:
            return
        self._done = True
        try:
            self._service._reply(self.source_node, self.request_id,
                                 response or {}, None)
        finally:
            self._service._finish_task(self)

    def send_failure(self, error: Exception) -> None:
        if self._done:
            return
        self._done = True
        try:
            self._service._reply(self.source_node, self.request_id, None,
                                 error)
        finally:
            self._service._finish_task(self)


@dataclass
class _RequestHandler:
    action: str
    handler: Callable                       # (request: dict, channel) -> None
    executor: str = "generic"               # "same" = run on delivery thread


@dataclass
class _ResponseContext:
    future: Future
    node: DiscoveryNode
    action: str
    timer: threading.Timer | None = None
    sent_at: float = field(default_factory=time.monotonic)


class TransportService:
    """One per node. Owns the handler registry and in-flight request table;
    delegates byte movement to a Transport (local.py / tcp.py)."""

    def __init__(self, transport, local_node_factory, executor=None,
                 thread_pool=None):
        """`local_node_factory(bound_address) -> DiscoveryNode` — the node
        identity depends on the port the transport binds. When the node's
        :class:`~elasticsearch_tpu.common.threadpool.ThreadPool` is given,
        named-executor dispatch runs on its bounded pools (rejections
        propagate to the caller as transport failures — backpressure);
        otherwise ad-hoc unbounded pools serve tests/standalone use."""
        self.transport = transport
        self._handlers: dict[str, _RequestHandler] = {}
        self._responses: dict[int, _ResponseContext] = {}
        # random start: a node restarted under its old id must not reuse
        # ids that a peer still remembers in its delivered window
        self._request_id = uuid.uuid4().int >> 88
        # (source node_id, request_id) of recent inbound requests, oldest
        # first: a frame the network delivers twice runs its handler once
        self._delivered: OrderedDict[tuple[str, int], None] = OrderedDict()
        self._lock = threading.Lock()
        self._executor = executor or ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="transport")
        self._owns_executor = executor is None
        self.thread_pool = thread_pool
        # Named per-workload pools (ThreadPool.java:70-129: index/bulk/
        # search/management...). Handlers that BLOCK on further RPCs (e.g.
        # a primary waiting for replica acks) must not share a pool with
        # the handlers they wait on, or two nodes writing to each other
        # deadlock when one pool saturates.
        self._pools: dict[str, ThreadPoolExecutor] = {}
        self._pools_lock = threading.Lock()
        self.tracers: list[Callable[[str, str, str], None]] = []
        # Service-level fault-injection seam (the MockTransportService
        # analog, one layer ABOVE the byte mover so it applies uniformly
        # to LocalTransport and TcpTransport): rule(addr, action) →
        # None | DROP | delay-seconds | ("duplicate", n) |
        # ("reorder", jitter-seconds). Evaluated on every outbound
        # request and response ("<response>" action, matching the
        # transport-level seams). Installed by testing_disruption
        # schemes; None in production.
        self.outbound_rule: Callable | None = None
        # TaskManager (tasks/manager.py), set by the node: every inbound
        # request registers a task, every outbound request carries the
        # current task's id as the parent link. None → no accounting
        # (standalone transports in unit tests).
        self.task_manager = None
        self._closed = False
        transport.bind(self)
        self.local_node: DiscoveryNode = local_node_factory(
            transport.bound_address())

    # ---- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        with self._lock:
            pending = list(self._responses.values())
            self._responses.clear()
        for ctx in pending:
            if ctx.timer:
                ctx.timer.cancel()
            if not ctx.future.done():
                ctx.future.set_exception(
                    NodeDisconnectedError("transport closed"))
        self.transport.close()
        if self._owns_executor:
            self._executor.shutdown(wait=False, cancel_futures=True)
        with self._pools_lock:
            for pool in self._pools.values():
                pool.shutdown(wait=False, cancel_futures=True)
            self._pools.clear()

    # ---- registry ----------------------------------------------------------

    def register_request_handler(self, action: str, handler,
                                 executor: str = "generic",
                                 sync: bool = False) -> None:
        """`handler(request: dict, channel: TransportChannel)`; with
        `sync=True`, `handler(request: dict, source: DiscoveryNode) -> dict`
        and the response/failure is sent automatically."""
        if sync:
            inner = handler

            def handler(request, channel, _fn=inner):
                try:
                    channel.send_response(_fn(request, channel.source_node))
                except Exception as e:          # noqa: BLE001 — crosses RPC
                    channel.send_failure(e)
        self._handlers[action] = _RequestHandler(action, handler, executor)

    # ---- outbound ----------------------------------------------------------

    def send_request(self, node: DiscoveryNode, action: str, request: dict,
                     timeout: float | None = None) -> Future:
        """Returns a Future resolving to the response dict."""
        fut: Future = Future()
        if self._closed:
            fut.set_exception(NodeDisconnectedError("transport closed"))
            return fut
        with self._lock:
            self._request_id += 1
            rid = self._request_id
            ctx = _ResponseContext(fut, node, action)
            self._responses[rid] = ctx
        self._trace("send_request", action, node.node_id)
        if self.task_manager is not None:
            # parent-task propagation (TaskId in the request envelope):
            # the receiver links its task under ours, making the fan-out
            # one visible tree — and cancellable as one
            from elasticsearch_tpu.tasks import TASK_HEADER, current_task
            cur = current_task()
            if cur is not None:
                request = {**request, TASK_HEADER: cur.task_id}
        # trace-context propagation rides the same envelope: the
        # receiver re-roots its spans under ours, so one search yields
        # ONE cross-node span tree keyed by the coordinating task id
        from elasticsearch_tpu.observability.tracing import (
            TRACE_HEADER, wire_header)
        trace_hdr = wire_header()
        if trace_hdr is not None:
            request = {**request, TRACE_HEADER: trace_hdr}
        if timeout is not None:
            ctx.timer = threading.Timer(timeout, self._on_timeout, (rid,))
            ctx.timer.daemon = True
            ctx.timer.start()
        out = StreamOutput(min(self.local_node.version, node.version))
        out.write_value(request)
        payload = out.bytes()
        try:
            self._ruled_send(
                node.address, action,
                lambda: self.transport.send_request(node, rid, action,
                                                    payload))
        except Exception as e:                  # noqa: BLE001 — connect errors
            self._complete(rid, None, e if isinstance(e, TransportException)
                           else ConnectTransportError(str(e)))
        return fut

    def submit_request(self, node, action, request, timeout=None) -> dict:
        """Blocking convenience (TransportFuture.txGet analog)."""
        return self.send_request(node, action, request, timeout).result(
            timeout=None if timeout is None else timeout + 5.0)

    # ---- inbound (called by the Transport impl) ----------------------------

    def on_request(self, source: DiscoveryNode, request_id: int, action: str,
                   payload: bytes, wire_version: int,
                   on_accept: Callable[[], None] | None = None) -> None:
        """Dispatch one inbound request. A redelivery of a request id
        already seen from ``source`` is dropped: the first copy's handler
        answers it, and a second run would answer differently (a
        duplicated primary delete finds the document gone). ``on_accept``
        runs for a first delivery only, before the handler can reply."""
        key = (source.node_id, request_id)
        with self._lock:
            if key in self._delivered:
                return
            self._delivered[key] = None
            if len(self._delivered) > DELIVERED_WINDOW:
                self._delivered.popitem(last=False)
        if on_accept is not None:
            on_accept()
        self._trace("recv_request", action, source.node_id)
        channel = TransportChannel(self, source, request_id, action)
        reg = self._handlers.get(action)
        if reg is None:
            channel.send_failure(ActionNotFoundError(action))
            return
        request = StreamInput(payload, wire_version).read_value()
        parent_task = None
        trace_hdr = None
        if isinstance(request, dict):
            from elasticsearch_tpu.observability.tracing import \
                TRACE_HEADER
            from elasticsearch_tpu.tasks import TASK_HEADER
            parent_task = request.pop(TASK_HEADER, None)
            trace_hdr = request.pop(TRACE_HEADER, None)
        if self.task_manager is not None:
            # register BEFORE dispatch so queue time on a saturated pool
            # is visible in the task list, and a ban that lands while the
            # request waits still cancels it before it runs a step
            channel.task = self.task_manager.register(
                action, description=f"requests[{source.name}]",
                parent_task_id=parent_task, task_type="transport")

        def run():
            from elasticsearch_tpu.observability.tracing import adopt
            from elasticsearch_tpu.tasks import use_task
            try:
                # spans record on the RECEIVING node's store, parented
                # under the sender's current span
                with use_task(channel.task), \
                        adopt(trace_hdr, self.local_node.node_id):
                    reg.handler(request, channel)
            except Exception as e:              # noqa: BLE001 — crosses RPC
                channel.send_failure(e)

        if reg.executor == "same" or self._closed:
            run()
        elif reg.executor == "generic":
            self._executor.submit(run)
        else:
            try:
                self._pool_for(reg.executor).submit(run)
            except Exception as e:              # noqa: BLE001 — rejection
                # bounded-pool rejection (EsRejectedExecutionError): the
                # caller gets the 429-class failure instead of unbounded
                # queueing — this IS the backpressure signal
                channel.send_failure(e)

    def on_response(self, request_id: int, payload: bytes | None,
                    error: tuple[str, str] | None,
                    wire_version: int) -> None:
        if error is not None:
            with self._lock:
                ctx = self._responses.get(request_id)
            name = ctx.node.name if ctx else "?"
            action = ctx.action if ctx else "?"
            self._complete(request_id, None,
                           RemoteTransportError(name, action, *error))
        else:
            self._complete(
                request_id, StreamInput(payload, wire_version).read_value(),
                None)

    def on_node_disconnected(self, node: DiscoveryNode) -> None:
        """Fail all in-flight requests targeting a dropped node
        (TransportService.java connection listener)."""
        with self._lock:
            dropped = [rid for rid, ctx in self._responses.items()
                       if ctx.node.node_id == node.node_id]
        for rid in dropped:
            self._complete(rid, None,
                           NodeDisconnectedError(f"[{node.name}] disconnected"))

    # ---- internals ---------------------------------------------------------

    def _finish_task(self, channel: "TransportChannel") -> None:
        """Unregister the request's task once its reply went out (or was
        dropped because the requester is gone) — the registry must never
        outlive the work it describes."""
        task, channel.task = channel.task, None
        if task is not None and self.task_manager is not None:
            self.task_manager.unregister(task)

    def _reply(self, to_node: DiscoveryNode, request_id: int,
               response: dict | None, error: Exception | None) -> None:
        self._trace("send_response", str(request_id), to_node.node_id)
        if error is not None:
            wire_err = (type(error).__name__, str(error))
            self._ruled_send(
                to_node.address, "<response>",
                lambda: self.transport.send_response(to_node, request_id,
                                                     None, wire_err))
        else:
            out = StreamOutput(min(self.local_node.version, to_node.version))
            out.write_value(response)
            payload = out.bytes()
            self._ruled_send(
                to_node.address, "<response>",
                lambda: self.transport.send_response(to_node, request_id,
                                                     payload, None))

    def _ruled_send(self, addr: "TransportAddress", action: str,
                    send: Callable[[], None]) -> None:
        """Apply the service-level fault rule, then move the bytes.
        Deferred sends (delay/reorder) fire on a timer and stay silent
        when the node died meanwhile — a resurrected stale send is the
        ghost-message class the disruption tests exist to rule out."""
        rule = self.outbound_rule
        verdict = rule(addr, action) if rule is not None else None
        if verdict is None:
            send()
            return
        if verdict == DROP:
            return
        if isinstance(verdict, (int, float)):
            if verdict <= 0:
                send()
                return
            self._deferred_send(float(verdict), send)
            return
        if isinstance(verdict, tuple) and len(verdict) == 2:
            kind, arg = verdict
            if kind == DUPLICATE:
                send()
                for _ in range(max(int(arg), 0)):
                    send()
                return
            if kind == REORDER:
                self._deferred_send(max(float(arg), 0.0), send)
                return
        raise ValueError(f"unknown fault verdict {verdict!r}")

    def _deferred_send(self, delay: float, send: Callable[[], None]) -> None:
        def fire():
            if self._closed:
                return
            try:
                send()
            except (OSError, TransportException):
                pass                             # target gone meanwhile
        t = threading.Timer(delay, fire)
        t.daemon = True
        t.start()

    def _pool_for(self, name: str):
        if self.thread_pool is not None:
            return self.thread_pool.executor(name)
        with self._pools_lock:
            pool = self._pools.get(name)
            if pool is None:
                pool = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix=f"transport-{name}")
                self._pools[name] = pool
            return pool

    def _complete(self, request_id: int, response: dict | None,
                  error: Exception | None) -> None:
        with self._lock:
            ctx = self._responses.pop(request_id, None)
        if ctx is None:
            return                               # late response after timeout
        if ctx.timer:
            ctx.timer.cancel()
        if ctx.future.done():
            return
        if error is not None:
            ctx.future.set_exception(error)
        else:
            ctx.future.set_result(response)

    def _on_timeout(self, request_id: int) -> None:
        with self._lock:
            ctx = self._responses.get(request_id)
        if ctx is None:
            return
        elapsed = time.monotonic() - ctx.sent_at
        self._complete(
            request_id, None,
            ReceiveTimeoutError(
                f"[{ctx.node.name}][{ctx.action}] request timed out after "
                f"{elapsed * 1e3:.0f}ms"))

    def _trace(self, event: str, action: str, node_id: str) -> None:
        for t in self.tracers:
            t(event, action, node_id)


def random_node_id() -> str:
    return uuid.uuid4().hex[:20]
