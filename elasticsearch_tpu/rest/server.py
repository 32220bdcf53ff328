"""HTTP ingress.

Reference: core/http/netty/NettyHttpServerTransport.java:63 +
core/http/HttpServer.java:47. A threaded stdlib HTTP server is the host
control-plane ingress (queries are device-bound; HTTP parsing is not the
bottleneck at the corpus sizes where TPU wins). Content type: JSON bodies,
NDJSON for _bulk, text/plain for _cat.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from elasticsearch_tpu.observability import tracing as obs_trace
from elasticsearch_tpu.rest.controller import RestController
from elasticsearch_tpu.rest.handlers import register_all


class RestServer:
    def __init__(self, node, host: str = "127.0.0.1", port: int = 9200):
        self.node = node
        self.controller = RestController()
        register_all(self.controller, node)
        plugins = getattr(node, "plugins_service", None)
        if plugins is not None:
            plugins.apply_rest(self.controller, node)
        controller = self.controller

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _handle(self):
                # one request id for the four spans of the rest layer
                # and everything the dispatch causes
                with obs_trace.request():
                    with obs_trace.span("rest.read"):
                        length = int(self.headers.get("Content-Length")
                                     or 0)
                        body = self.rfile.read(length) if length else b""
                    with obs_trace.span("rest.handle"):
                        status, payload = controller.dispatch(
                            self.command, self.path, body,
                            content_type=self.headers.get("Content-Type"))
                    with obs_trace.span("rest.serialise"):
                        data, ctype = self._serialise(payload)
                    with obs_trace.span("rest.write"):
                        self.send_response(status)
                        self.send_header("Content-Type", ctype)
                        self.send_header("Content-Length", str(len(data)))
                        self.end_headers()
                        if self.command != "HEAD":
                            self.wfile.write(data)

            def _serialise(self, payload):
                if isinstance(payload, str):
                    return (payload.encode("utf-8"),
                            "text/plain; charset=UTF-8")
                # response format: ?format= wins, else the Accept
                # header (XContentType.fromMediaTypeOrFormat)
                from urllib.parse import parse_qs, urlparse
                from elasticsearch_tpu.common.xcontent import encode
                qs = parse_qs(urlparse(self.path).query,
                              keep_blank_values=True)
                fmt = (qs.get("format") or [None])[0]
                accept = fmt or self.headers.get("Accept")
                if accept in ("*/*", "", None):
                    accept = "json"
                # bare `?pretty` means true (param_as_bool semantics)
                pretty = (qs.get("pretty") or ["false"])[0] \
                    in ("", "true", "1")
                try:
                    data, ctype = encode(payload, accept, pretty=pretty)
                except Exception:   # noqa: BLE001 — never drop the
                    # connection over a response-format failure
                    data, ctype = (json.dumps(payload,
                                              default=str).encode(),
                                   "application/json")
                return data, ctype + "; charset=UTF-8"

            do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _handle

            def log_message(self, fmt, *args):  # quiet access log
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = self._server.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> "RestServer":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True, name="rest-server")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
