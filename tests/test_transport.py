"""Transport layer tests: wire codec round-trips, local + TCP RPC,
timeouts, error propagation, disruption drops.

Mirrors the reference's transport unit tests
(core/src/test/java/org/elasticsearch/transport/AbstractSimpleTransportTests
style: register handler, send, assert response/exceptions)."""

import threading
import time

import pytest

from elasticsearch_tpu.transport import (
    ActionNotFoundError, DiscoveryNode, LocalTransport, LocalTransportHub,
    ReceiveTimeoutError, RemoteTransportError, StreamInput, StreamOutput,
    TcpTransport, TransportService,
)
from elasticsearch_tpu.transport.local import DROP
from elasticsearch_tpu.transport.service import DUPLICATE, random_node_id


# ---- wire codec ------------------------------------------------------------

def roundtrip(value):
    out = StreamOutput()
    out.write_value(value)
    return StreamInput(out.bytes()).read_value()


def test_stream_scalars():
    for v in [None, True, False, 0, 1, -1, 2**40, -(2**40), 3.5, "héllo",
              b"\x00\xff", "", []]:
        assert roundtrip(v) == v


def test_stream_nested():
    v = {"a": [1, {"b": None, "c": [True, "x"]}], "d": 2.25,
         "e": {"f": b"raw"}}
    assert roundtrip(v) == v


def test_stream_vint_boundaries():
    out = StreamOutput()
    for v in [0, 127, 128, 16383, 16384, 2**31, 2**62]:
        out.write_vint(v)
    inp = StreamInput(out.bytes())
    for v in [0, 127, 128, 16383, 16384, 2**31, 2**62]:
        assert inp.read_vint() == v


def test_stream_zlong():
    out = StreamOutput()
    for v in [0, -1, 1, -(2**40), 2**40]:
        out.write_zlong(v)
    inp = StreamInput(out.bytes())
    for v in [0, -1, 1, -(2**40), 2**40]:
        assert inp.read_zlong() == v


def test_stream_truncation_raises():
    out = StreamOutput()
    out.write_string("hello")
    with pytest.raises(EOFError):
        StreamInput(out.bytes()[:3]).read_string()


def test_discovery_node_wire():
    n = DiscoveryNode("id1", "name1",
                      address=__import__(
                          "elasticsearch_tpu.transport.service",
                          fromlist=["TransportAddress"]
                      ).TransportAddress("h", 9300),
                      attributes=(("data", "true"), ("master", "false")))
    out = StreamOutput()
    n.to_wire(out)
    assert DiscoveryNode.from_wire(StreamInput(out.bytes())) == n


# ---- local transport -------------------------------------------------------

def make_local_service(hub, name):
    t = LocalTransport(hub)
    return TransportService(
        t, lambda addr: DiscoveryNode(random_node_id(), name, addr))


@pytest.fixture
def pair():
    hub = LocalTransportHub()
    a = make_local_service(hub, "node_a")
    b = make_local_service(hub, "node_b")
    yield a, b
    a.close()
    b.close()


def test_local_request_response(pair):
    a, b = pair
    b.register_request_handler(
        "test:echo", lambda req, src: {"echo": req["msg"], "via": src.name},
        sync=True)
    resp = a.submit_request(b.local_node, "test:echo", {"msg": "hi"},
                            timeout=5.0)
    assert resp == {"echo": "hi", "via": "node_a"}


def test_local_remote_error(pair):
    a, b = pair

    def boom(req, src):
        raise ValueError("kapow")
    b.register_request_handler("test:boom", boom, sync=True)
    with pytest.raises(RemoteTransportError) as ei:
        a.submit_request(b.local_node, "test:boom", {}, timeout=5.0)
    assert ei.value.error_type == "ValueError"
    assert "kapow" in ei.value.reason


def test_local_unknown_action(pair):
    a, b = pair
    with pytest.raises(RemoteTransportError) as ei:
        a.submit_request(b.local_node, "test:nope", {}, timeout=5.0)
    assert ei.value.error_type == "ActionNotFoundError"


def test_local_timeout(pair):
    a, b = pair
    release = threading.Event()

    def slow(req, channel):
        release.wait(5.0)
        channel.send_response({})
    b.register_request_handler("test:slow", slow)
    with pytest.raises(ReceiveTimeoutError):
        a.submit_request(b.local_node, "test:slow", {}, timeout=0.1)
    release.set()


def test_local_disruption_drop(pair):
    a, b = pair
    b.register_request_handler("test:echo", lambda r, s: r, sync=True)
    a.transport.outbound_rule = \
        lambda addr, action: DROP if action == "test:echo" else None
    with pytest.raises(ReceiveTimeoutError):
        a.submit_request(b.local_node, "test:echo", {"x": 1}, timeout=0.2)
    a.transport.outbound_rule = None
    assert a.submit_request(b.local_node, "test:echo", {"x": 1},
                            timeout=5.0) == {"x": 1}


def test_local_concurrent_requests(pair):
    a, b = pair
    b.register_request_handler(
        "test:double", lambda req, src: {"v": req["v"] * 2}, sync=True)
    futs = [a.send_request(b.local_node, "test:double", {"v": i},
                           timeout=10.0) for i in range(50)]
    assert [f.result(10.0)["v"] for f in futs] == [2 * i for i in range(50)]


def test_async_handler_channel(pair):
    """Handlers doing nested RPC respond via channel later (replication
    style: primary acks only after replica round-trips)."""
    a, b = pair
    b.register_request_handler("test:inner", lambda r, s: {"inner": True},
                               sync=True)

    def outer(req, channel):
        fut = b.send_request(a.local_node, "test:pong", {}, timeout=5.0)
        fut.add_done_callback(
            lambda f: channel.send_response({"chained": f.result()}))
    b.register_request_handler("test:outer", outer)
    a.register_request_handler("test:pong", lambda r, s: {"pong": 1},
                               sync=True)
    resp = a.submit_request(b.local_node, "test:outer", {}, timeout=5.0)
    assert resp == {"chained": {"pong": 1}}


# ---- tcp transport ---------------------------------------------------------

@pytest.fixture
def tcp_pair():
    a = TransportService(
        TcpTransport(),
        lambda addr: DiscoveryNode(random_node_id(), "tcp_a", addr))
    b = TransportService(
        TcpTransport(),
        lambda addr: DiscoveryNode(random_node_id(), "tcp_b", addr))
    yield a, b
    a.close()
    b.close()


def test_tcp_request_response(tcp_pair):
    a, b = tcp_pair
    b.register_request_handler(
        "test:echo", lambda req, src: {"echo": req, "from": src.name},
        sync=True)
    resp = a.submit_request(b.local_node, "test:echo",
                            {"msg": "over tcp", "n": 42}, timeout=10.0)
    assert resp["echo"] == {"msg": "over tcp", "n": 42}
    assert resp["from"] == "tcp_a"


def test_tcp_error_and_many(tcp_pair):
    a, b = tcp_pair

    def maybe_boom(req, src):
        if req["v"] % 7 == 3:
            raise RuntimeError(f"boom {req['v']}")
        return {"v": req["v"] + 1}
    b.register_request_handler("test:m", maybe_boom, sync=True)
    futs = [a.send_request(b.local_node, "test:m", {"v": i}, timeout=10.0)
            for i in range(30)]
    for i, f in enumerate(futs):
        if i % 7 == 3:
            with pytest.raises(RemoteTransportError):
                f.result(10.0)
        else:
            assert f.result(10.0) == {"v": i + 1}


@pytest.mark.parametrize("kind", ["local", "tcp"])
def test_duplicated_request_runs_its_handler_once(kind):
    """A frame the network delivers twice runs the handler once: the
    caller sees the first run's answer, never a second run's (a
    duplicated delete would find the document gone), and the TCP side
    keeps no reply channel for the dropped copy."""
    if kind == "local":
        hub = LocalTransportHub()
        a, b = (make_local_service(hub, n) for n in ("node_a", "node_b"))
    else:
        a, b = (TransportService(
            TcpTransport(),
            lambda addr, n=n: DiscoveryNode(random_node_id(), n, addr))
            for n in ("tcp_a", "tcp_b"))
    try:
        docs, runs = {"d": 1}, []

        def delete(req, src):
            runs.append(req["id"])
            if docs.pop(req["id"], None) is None:
                raise KeyError(f"[{req['id']}] missing")
            return {"deleted": req["id"]}
        b.register_request_handler("test:delete", delete, sync=True)
        a.outbound_rule = lambda addr, action: \
            (DUPLICATE, 2) if action == "test:delete" else None
        assert a.submit_request(b.local_node, "test:delete", {"id": "d"},
                                timeout=5.0) == {"deleted": "d"}
        a.outbound_rule = None
        # a later request from the same sender still runs: the window
        # holds ids, not senders
        with pytest.raises(RemoteTransportError):
            a.submit_request(b.local_node, "test:delete", {"id": "d"},
                             timeout=5.0)
        assert runs == ["d", "d"]
        if kind == "tcp":
            assert b.transport._inbound_channels == {}
    finally:
        a.close()
        b.close()


def test_tcp_connect_failure():
    a = TransportService(
        TcpTransport(),
        lambda addr: DiscoveryNode(random_node_id(), "tcp_a", addr))
    try:
        from elasticsearch_tpu.transport.service import TransportAddress
        ghost = DiscoveryNode("ghost", "ghost", TransportAddress("127.0.0.1",
                                                                 1))
        from elasticsearch_tpu.transport import ConnectTransportError
        with pytest.raises(ConnectTransportError):
            a.submit_request(ghost, "x", {}, timeout=2.0)
    finally:
        a.close()


def test_tcp_compression_roundtrip():
    """transport.tcp.compress: large frames deflate on the wire (the
    reference's optional LZF bit, NettyTransport `transport.tcp.compress`)
    and a non-compressing peer still interoperates (per-frame flag)."""
    a = TransportService(
        TcpTransport(compress=True),
        lambda addr: DiscoveryNode(random_node_id(), "tcp_a", addr))
    b = TransportService(
        TcpTransport(),                 # replies uncompressed
        lambda addr: DiscoveryNode(random_node_id(), "tcp_b", addr))
    try:
        big = {"blob": "x" * 50_000, "n": 1}
        b.register_request_handler(
            "test:echo", lambda req, src: {"len": len(req["blob"])},
            sync=True)
        resp = a.submit_request(b.local_node, "test:echo", big,
                                timeout=10.0)
        assert resp == {"len": 50_000}
        # tiny frames skip compression (threshold)
        resp = a.submit_request(b.local_node, "test:echo",
                                {"blob": "y", "n": 2}, timeout=10.0)
        assert resp == {"len": 1}
    finally:
        a.close()
        b.close()


def test_tcp_channel_classes():
    """Outbound sockets are per traffic class (NettyTransport
    connectToNode channel groups): a recovery send and a ping send to the
    same peer use DIFFERENT sockets."""
    from elasticsearch_tpu.transport.tcp import channel_class
    assert channel_class("internal:index/shard/recovery[file_chunk]") == \
        "recovery"
    assert channel_class("indices:data/write/bulk[s]") == "bulk"
    assert channel_class("internal:discovery/zen/fd/master_ping") == "ping"
    assert channel_class("internal:discovery/zen/publish/send") == "state"
    assert channel_class("indices:data/read/search[phase/query]") == "reg"

    a = TransportService(
        TcpTransport(),
        lambda addr: DiscoveryNode(random_node_id(), "tcp_a", addr))
    b = TransportService(
        TcpTransport(),
        lambda addr: DiscoveryNode(random_node_id(), "tcp_b", addr))
    try:
        b.register_request_handler("internal:discovery/zen/fd/ping",
                                   lambda r, s: {"ok": 1}, sync=True)
        b.register_request_handler("indices:data/write/bulk",
                                   lambda r, s: {"ok": 2}, sync=True)
        assert a.submit_request(b.local_node,
                                "internal:discovery/zen/fd/ping", {},
                                timeout=10.0) == {"ok": 1}
        assert a.submit_request(b.local_node, "indices:data/write/bulk",
                                {}, timeout=10.0) == {"ok": 2}
        tcp = a._transport if hasattr(a, "_transport") else a.transport
        keys = {cls for (_addr, cls) in tcp._outbound}
        assert {"ping", "bulk"} <= keys
    finally:
        a.close()
        b.close()
