"""TcpTransport — length-framed binary RPC over real sockets.

Reference: core/transport/netty/NettyTransport.java:142 — 'E','S' marker +
4-byte length framing (NettyHeader.java, SizeHeaderFrameDecoder.java),
request/response status byte, request-id correlation, per-node channel
reuse (:871 `connectToNode`), version negotiation via min(local, remote)
on each frame. Threading: an accept loop + one reader thread per inbound
connection replaces the Netty event loop; handler dispatch happens on the
TransportService executor, matching the reference's worker offload.

Two NettyTransport disciplines carried over:
* optional frame compression (`transport.tcp.compress`, the LZF-optional
  bit of the reference's status byte — zlib here) — a flags byte after
  the marker, so each frame states whether its body is compressed;
* per-traffic-class outbound channels (connectToNode :871 opens
  recovery/bulk/reg/state/ping channel groups): the outbound socket is
  keyed by (address, class-of-action), so a bulk or recovery stream
  can't head-of-line-block pings or cluster-state publishes.

Frame layout: b"ET", 1 flags byte (bit0 = deflate), 4-byte big-endian
length, then the (possibly deflated) body:
  StreamOutput[ byte msg_type (0=req, 1=resp, 2=resp_error),
                long request_id, vint wire_version, then per type:
    req:        DiscoveryNode source, string action, bytes payload
    resp:       bytes payload
    resp_error: string error_type, string reason ]
"""

from __future__ import annotations

import socket
import struct
import threading
import zlib

from elasticsearch_tpu.transport.service import (
    ConnectTransportError, DiscoveryNode, TransportAddress)
from elasticsearch_tpu.transport.stream import (
    CURRENT_VERSION, StreamInput, StreamOutput)

_MARKER = b"ET"
_REQ, _RESP, _RESP_ERR = 0, 1, 2
_FLAG_COMPRESSED = 0x01
# compressing tiny frames (pings, acks) costs more than it saves
_COMPRESS_MIN_BYTES = 128

# action name → channel class, the reference's ChannelType routing
# (NettyTransport.connectToNode: recovery/bulk/reg/state/ping groups)
_CHANNEL_CLASSES = (
    ("internal:index/shard/recovery", "recovery"),
    ("indices:data/write", "bulk"),
    ("internal:discovery/zen/publish", "state"),
    ("cluster:monitor/state", "state"),
    ("internal:discovery/zen/fd", "ping"),
    ("internal:discovery/zen/unicast", "ping"),
)


def channel_class(action: str) -> str:
    for prefix, cls in _CHANNEL_CLASSES:
        if action.startswith(prefix):
            return cls
    return "reg"


class TcpTransport:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 publish_host: str | None = None,
                 compress: bool = False):
        self._host, self._want_port = host, port
        self.compress = compress
        # the address peers should dial (ref: `transport.publish_host` /
        # NetworkService publish resolution): binding to a wildcard must
        # not advertise the wildcard, which dials back to the PEER's own
        # loopback
        self._publish_host = publish_host
        self._service = None
        self._address: TransportAddress | None = None
        self._server: socket.socket | None = None
        self._closed = False
        self._lock = threading.Lock()
        # outbound sockets keyed by (address, channel class)
        self._outbound: dict[tuple[TransportAddress, str],
                             socket.socket] = {}
        # reply channels keyed by (requester node_id, its request_id):
        # request ids are per-requester counters, so two clients' ids collide
        self._inbound_channels: dict[tuple[str, int], socket.socket] = {}
        # every accepted connection, so close() can sever them — a killed
        # node must not process frames already in flight on inbound socks
        self._inbound_socks: set[socket.socket] = set()
        # one writer lock per live socket — sendall releases the GIL between
        # chunks, so unserialized concurrent writers interleave frames
        self._write_locks: dict[int, threading.Lock] = {}
        self._threads: list[threading.Thread] = []
        # Disruption hook: rule(to_address, action) -> None | "drop" | float
        # (seconds of delay) — same seam LocalTransport exposes, so the
        # disruption schemes (testing_disruption.py) run over real sockets.
        self.outbound_rule = None

    # ---- Transport interface ----------------------------------------------

    def bind(self, service) -> None:
        self._service = service
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((self._host, self._want_port))
        srv.listen(64)
        self._server = srv
        publish = self._publish_host or self._host
        if publish in ("0.0.0.0", "::", ""):
            publish = self._default_publish_host()
        self._address = TransportAddress(publish, srv.getsockname()[1])
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name=f"tcp_accept[{self._address}]")
        t.start()
        self._threads.append(t)

    def bound_address(self) -> TransportAddress:
        return self._address

    @staticmethod
    def _default_publish_host() -> str:
        """Best routable local address when bound to a wildcard: the source
        address of an (unsent) UDP connect to a public IP, falling back to
        the hostname's resolution, then loopback."""
        try:
            probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                probe.connect(("8.8.8.8", 9))
                return probe.getsockname()[0]
            finally:
                probe.close()
        except OSError:
            pass
        try:
            return socket.gethostbyname(socket.gethostname())
        except OSError:
            return "127.0.0.1"

    def close(self) -> None:
        self._closed = True
        try:
            self._server.close()
        except OSError:
            pass
        with self._lock:
            socks = list(self._outbound.values()) + \
                list(self._inbound_socks)
            self._outbound.clear()
            self._inbound_socks.clear()
            self._write_locks.clear()
        for s in socks:
            try:
                # shutdown unblocks reader threads parked in recv() so no
                # already-inflight frame gets dispatched after the kill
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def _ruled(self, addr: TransportAddress, action: str,
               send) -> bool:
        """Apply the disruption rule; → True when the send was handled
        (dropped or deferred), False when the caller should send now."""
        rule = self.outbound_rule
        if rule is None:
            return False
        verdict = rule(addr, action)
        if verdict == "drop":
            return True
        if isinstance(verdict, (int, float)) and verdict > 0:
            def fire():
                # a node killed while the delay was pending must stay
                # silent (LocalTransport._deliver's _closed guard): a
                # resurrected stale send is exactly the ghost-message
                # class the disruption tests exist to rule out
                if self._closed:
                    return
                try:
                    send()
                except (OSError, ConnectTransportError):
                    pass                         # target gone meanwhile
            t = threading.Timer(float(verdict), fire)
            t.daemon = True
            t.start()
            return True
        return False

    def send_request(self, node: DiscoveryNode, request_id: int, action: str,
                     payload: bytes) -> None:
        if self._ruled(node.address, action,
                       lambda: self._do_send_request(node, request_id,
                                                     action, payload)):
            return
        self._do_send_request(node, request_id, action, payload)

    def _do_send_request(self, node: DiscoveryNode, request_id: int,
                         action: str, payload: bytes) -> None:
        # the ENVELOPE serializes at the negotiated version too — gated
        # fields inside DiscoveryNode.to_wire key off out.version
        wire_version = min(self._service.local_node.version, node.version)
        out = StreamOutput(wire_version)
        out.write_byte(_REQ)
        out.write_long(request_id)
        out.write_vint(wire_version)
        self._service.local_node.to_wire(out)
        out.write_string(action)
        out.write_bytes(payload)
        self._send_frame(node.address, out.bytes(), channel_class(action))

    def send_response(self, node: DiscoveryNode, request_id: int,
                      payload: bytes | None, error) -> None:
        # pop the reply channel BEFORE the disruption rule: a dropped
        # response must not leak the (node_id, request_id) → socket entry
        with self._lock:
            chan = self._inbound_channels.pop((node.node_id, request_id),
                                              None)
        if self._ruled(node.address, "<response>",
                       lambda: self._do_send_response(node, request_id,
                                                      payload, error, chan)):
            return
        self._do_send_response(node, request_id, payload, error, chan)

    def _do_send_response(self, node: DiscoveryNode, request_id: int,
                          payload: bytes | None, error,
                          chan: socket.socket | None = None) -> None:
        # response envelope serializes at the negotiated version, same
        # as the request path
        wire_version = min(self._service.local_node.version, node.version)
        out = StreamOutput(wire_version)
        if error is None:
            out.write_byte(_RESP)
            out.write_long(request_id)
            out.write_vint(wire_version)
            out.write_bytes(payload)
        else:
            out.write_byte(_RESP_ERR)
            out.write_long(request_id)
            out.write_vint(wire_version)
            out.write_string(error[0])
            out.write_string(error[1])
        # Prefer the inbound channel the request arrived on (the reference
        # replies on the request's channel); fall back to an outbound conn.
        if chan is not None:
            try:
                self._write_framed(chan, out.bytes())
                return
            except OSError:
                pass
        try:
            self._send_frame(node.address, out.bytes(), "reg")
        except ConnectTransportError:
            pass                                 # requester is gone

    # ---- socket plumbing ---------------------------------------------------

    def _send_frame(self, addr: TransportAddress, body: bytes,
                    cls: str = "reg") -> None:
        sock = self._connect(addr, cls)
        try:
            self._write_framed(sock, body)
        except OSError as e:
            with self._lock:
                self._outbound.pop((addr, cls), None)
                self._write_locks.pop(id(sock), None)
            raise ConnectTransportError(f"send to {addr} failed: {e}") from e

    def _write_framed(self, sock: socket.socket, body: bytes) -> None:
        flags = 0
        if self.compress and len(body) >= _COMPRESS_MIN_BYTES:
            body = zlib.compress(body, 6)
            flags |= _FLAG_COMPRESSED
        with self._lock:
            wl = self._write_locks.setdefault(id(sock), threading.Lock())
        with wl:
            sock.sendall(_MARKER + bytes([flags])
                         + struct.pack(">i", len(body)) + body)

    def _connect(self, addr: TransportAddress,
                 cls: str = "reg") -> socket.socket:
        if self._closed:
            # a killed node must not dial fresh connections: a handler
            # thread that outlived close() could otherwise ACK a write
            # whose replica fan-out was failed by that very close — the
            # promoted replica then misses an acked doc (chaos-matrix
            # find: master kill racing a bulk)
            raise ConnectTransportError("transport closed")
        key = (addr, cls)
        with self._lock:
            sock = self._outbound.get(key)
        if sock is not None:
            return sock
        try:
            sock = socket.create_connection((addr.host, addr.port),
                                            timeout=5.0)
        except OSError as e:
            raise ConnectTransportError(f"connect to {addr} failed: {e}") \
                from e
        sock.settimeout(None)
        with self._lock:
            existing = self._outbound.get(key)
            if existing is not None:
                sock.close()
                return existing
            self._outbound[key] = sock
        t = threading.Thread(target=self._read_loop, args=(sock,),
                             daemon=True, name=f"tcp_read[{addr}/{cls}]")
        t.start()
        self._threads.append(t)
        return sock

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _ = self._server.accept()
            except OSError:
                return
            with self._lock:
                if self._closed:
                    conn.close()
                    continue
                self._inbound_socks.add(conn)
            t = threading.Thread(target=self._read_loop, args=(conn,),
                                 daemon=True, name="tcp_read[inbound]")
            t.start()
            self._threads.append(t)

    def _read_loop(self, sock: socket.socket) -> None:
        try:
            while not self._closed:
                header = self._read_exact(sock, 7)
                if header is None:
                    return
                if header[:2] != _MARKER:
                    return                       # corrupt stream: drop conn
                flags = header[2]
                size = struct.unpack(">i", header[3:])[0]
                body = self._read_exact(sock, size)
                if body is None:
                    return
                if flags & _FLAG_COMPRESSED:
                    try:
                        body = zlib.decompress(body)
                    except zlib.error:
                        return                   # corrupt stream: drop conn
                self._handle_frame(sock, body)
        except OSError:
            return
        finally:
            with self._lock:
                self._write_locks.pop(id(sock), None)
                self._inbound_socks.discard(sock)
            try:
                sock.close()
            except OSError:
                pass

    @staticmethod
    def _read_exact(sock: socket.socket, n: int) -> bytes | None:
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                return None
            buf += chunk
        return buf

    def _handle_frame(self, sock: socket.socket, body: bytes) -> None:
        inp = StreamInput(body)
        msg_type = inp.read_byte()
        request_id = inp.read_long()
        version = inp.read_vint()
        # everything after the version vint — including the envelope's
        # DiscoveryNode — parses at the declared stream version
        inp.version = version
        if msg_type == _REQ:
            source = DiscoveryNode.from_wire(inp)
            action = inp.read_string()
            payload = inp.read_bytes()

            def register_channel():
                # first delivery only: a dropped duplicate must not leave
                # a reply channel that no response will ever pop
                with self._lock:
                    self._inbound_channels[(source.node_id, request_id)] = \
                        sock
            self._service.on_request(source, request_id, action, payload,
                                     version, on_accept=register_channel)
        elif msg_type == _RESP:
            self._service.on_response(request_id, inp.read_bytes(), None,
                                      version)
        elif msg_type == _RESP_ERR:
            err = (inp.read_string(), inp.read_string())
            self._service.on_response(request_id, None, err, version)
