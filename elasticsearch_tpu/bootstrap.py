"""CLI entry point (`estpu`).

Reference: core/bootstrap/Elasticsearch.java:33 → Bootstrap.setup/start —
CLI parse, environment prep, node start, HTTP ingress last, then wait.
(The reference's mlockall/seccomp hardening is JVM-era host glue; the
analogous concerns here — device memory pinning and sandboxing — belong to
the TPU runtime/XLA.)
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest.server import RestServer


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="estpu", description="elasticsearch-tpu node")
    parser.add_argument("--data", default="data", help="data directory")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=9200)
    parser.add_argument("--cpu", action="store_true",
                        help="force JAX CPU platform (no TPU)")
    parser.add_argument("-E", action="append", default=[], metavar="K=V",
                        help="setting override (repeatable)")
    parser.add_argument("--portsfile", default=None,
                        help="write 'http=<port>\\ntransport=<port>' here "
                             "once bound (test orchestration; ref: the "
                             "--portsfile node flag)")
    args = parser.parse_args(argv)

    if args.cpu:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")

    # the first log line names the device this node will serve from: a
    # node that was meant for a TPU and found none says so here
    from elasticsearch_tpu.common.device import describe_devices
    dev = describe_devices()
    print(f"[estpu] device: platform={dev['platform']} "
          f"kind={dev['kind']} count={dev['count']}"
          + ("" if dev["platform"] == "tpu" or args.cpu else
             " — NO TPU FOUND, serving from the CPU backend "
             "(pass --cpu to choose this on purpose)"), flush=True)

    overrides = {}
    for kv in args.E:
        k, _, v = kv.partition("=")
        overrides[k] = v
    settings = Settings({"path.data": args.data, **overrides})

    node = Node(settings, data_path=args.data).start()
    server = RestServer(node, host=args.host, port=args.port).start()
    taddr = node.transport_service.transport.bound_address()
    print(f"[estpu] node [{node.node_name}] started, "
          f"http on {server.host}:{server.port}, transport on {taddr}",
          flush=True)
    if args.portsfile:
        from pathlib import Path
        Path(args.portsfile).write_text(
            f"http={server.port}\ntransport={taddr.port}\n")

    stop = threading.Event()

    def handle(sig, frame):
        stop.set()

    signal.signal(signal.SIGINT, handle)
    signal.signal(signal.SIGTERM, handle)
    stop.wait()
    print("[estpu] stopping", flush=True)
    server.stop()
    node.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
