#!/usr/bin/env python3
"""The one general load generator. A separate OS process that never
imports JAX (the server process holds the chip): it reads a job file, opens
its keep-alive connections, sends what the job says over a real socket, and
writes what it saw from the client's side.

    python3 benchmarks/loadgen.py <job.json>

Job: ``host``, ``port``, ``mode`` (``closed``: each connection sends its
stream's next request when the reply is in; ``open``: every request is sent
when it is due, whatever has come back), ``streams``
(closed: one list of requests per connection) or ``requests`` (open: sorted
by ``due``) with ``connections``, ``save`` (request ids whose reply is kept
for the comparison), ``out``. Once every connection is open and has had one
warm request the generator writes ``<out>.ready`` and waits for the file
``go``, which holds ``t_start`` and ``t_end`` on ``time.monotonic()`` (one
clock for all processes of a machine). A request is ``{"id", "path", "body",
"items"}`` and, in an open loop, ``"due"`` in seconds after ``t_start``.

Out: ``records`` = ``[id, due, sent, done, http_status, items_ok, items]``
(monotonic seconds; ``done`` is when the whole reply was read) and
``saved`` = ``{id: reply text}``.
"""

from __future__ import annotations

import http.client
import json
import os
import queue
import re
import sys
import threading
import time

OK_SHARDS = re.compile(rb'"failed": ?0\b')
NOT_TIMED_OUT = re.compile(rb'"timed_out": ?false')
ERROR = re.compile(rb'"error"')


def items_ok(status: int, raw: bytes, items: int) -> int:
    """How many of a reply's searches were answered without failure: HTTP
    2xx, no error entry, every item with no failed shard and not timed
    out. A refused (429) or failed request answers none."""
    if status >= 300 or ERROR.search(raw):
        if status < 300 and items > 1:
            # an _msearch reply with some failed items: count the good ones
            try:
                return sum(1 for r in json.loads(raw)["responses"]
                           if "error" not in r and not r.get("timed_out")
                           and r["_shards"]["failed"] == 0)
            except (ValueError, KeyError):
                return 0
        return 0
    good = min(len(OK_SHARDS.findall(raw)), len(NOT_TIMED_OUT.findall(raw)))
    return min(good, items)


class Conn:
    def __init__(self, job: dict):
        self.job = job
        self.conn = None
        self.connect()

    def connect(self) -> None:
        self.conn = http.client.HTTPConnection(
            self.job["host"], self.job["port"],
            timeout=self.job["timeout_s"])
        self.conn.connect()

    def send(self, req: dict):
        """→ (sent, done, status, raw)."""
        sent = time.monotonic()
        try:
            self.conn.request("POST", req["path"], body=req["body"],
                              headers={"Content-Type": "application/json"})
            resp = self.conn.getresponse()
            raw = resp.read()
            return sent, time.monotonic(), resp.status, raw
        except (OSError, http.client.HTTPException):
            done = time.monotonic()
            try:
                self.conn.close()
                self.connect()
            except OSError:
                pass
            return sent, done, 599, b""


class Recorder:
    def __init__(self, job: dict):
        self.save = set(job.get("save", []))
        self.records: list = []
        self.saved: dict = {}
        self.lock = threading.Lock()

    def note(self, req: dict, due, sent, done, status, raw) -> None:
        ok = items_ok(status, raw, req["items"])
        with self.lock:
            self.records.append([req["id"], due, sent, done, status, ok,
                                 req["items"]])
            if req["id"] in self.save and ok == req["items"]:
                self.saved[str(req["id"])] = raw.decode()


def warm(conn: Conn) -> None:
    """One cheap request on each connection before the window, so that
    every socket is open and has its server thread when it starts."""
    conn.conn.request("GET", "/")
    conn.conn.getresponse().read()


def await_go(job: dict) -> None:
    """Say that every connection is open, then wait to be told when the
    window starts and ends."""
    with open(job["out"] + ".ready", "w") as f:
        f.write("ready\n")
    deadline = time.monotonic() + 600
    while not os.path.exists(job["go"]):
        if time.monotonic() > deadline:
            raise SystemExit("no go file within 600 s")
        time.sleep(0.005)
    with open(job["go"]) as f:
        job.update(json.load(f))


def closed_loop(job: dict, rec: Recorder) -> None:
    def client(stream: list) -> None:
        conn = Conn(job)
        warm(conn)
        opened.wait()
        ready.wait()
        time.sleep(max(0.0, job["t_start"] - time.monotonic()))
        for req in stream:
            if time.monotonic() >= job["t_end"]:
                break
            sent, done, status, raw = conn.send(req)
            rec.note(req, sent, sent, done, status, raw)
        conn.conn.close()

    ready = threading.Event()
    opened = threading.Barrier(len(job["streams"]) + 1)
    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in job["streams"]]
    for t in threads:
        t.start()
    opened.wait()
    await_go(job)
    ready.set()
    for t in threads:
        t.join(job["t_end"] - time.monotonic() + job["timeout_s"] + 5)


def open_loop(job: dict, rec: Recorder) -> None:
    todo: queue.Queue = queue.Queue()
    opened = threading.Barrier(job["connections"] + 1)

    def worker() -> None:
        conn = Conn(job)
        warm(conn)
        opened.wait()
        while True:
            item = todo.get()
            if item is None:
                break
            req, due = item
            sent, done, status, raw = conn.send(req)
            rec.note(req, due, sent, done, status, raw)
        conn.conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(job["connections"])]
    for t in threads:
        t.start()
    opened.wait()
    await_go(job)
    t0 = job["t_start"]
    for req in job["requests"]:
        due = t0 + req["due"]
        if due >= job["t_end"]:
            break
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put((req, due))
    for _ in threads:
        todo.put(None)
    deadline = job["t_end"] + job["timeout_s"] + 5
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))


def main(argv: list) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    rec = Recorder(job)
    (open_loop if job["mode"] == "open" else closed_loop)(job, rec)
    with rec.lock:
        out = {"records": list(rec.records), "saved": dict(rec.saved)}
    with open(job["out"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
