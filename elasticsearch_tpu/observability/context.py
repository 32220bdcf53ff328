"""Node attribution context — WHICH node's books an event lands on.

Every in-process node shares one Python process (and one device), so
module-level observability state (trace stores, latency histograms, the
jit counter rollups) must be keyed by node id or multi-node cluster
tests smear one node's activity into every node's ``_nodes/stats``. The
node id of the moment comes from, in order:

1. an explicit :func:`use_node` override (background pools that work on
   behalf of a node without a task — the plane warm pool);
2. the thread's current :class:`~elasticsearch_tpu.tasks.manager.Task`
   (the transport layer registers one per inbound request, and
   ``bind_current`` carries it across pool submits), whose ``node_id``
   is the node that registered it.

``None`` means "unattributed" — counters still land on the process-wide
rollup, just not on any node's bucket.
"""

from __future__ import annotations

import contextlib
import threading

from elasticsearch_tpu.tasks.manager import current_task

_tls = threading.local()


def current_node_id() -> str | None:
    nid = getattr(_tls, "node_id", None)
    if nid is not None:
        return nid
    task = current_task()
    return task.node_id if task is not None else None


@contextlib.contextmanager
def use_node(node_id: str | None):
    """Attribute observability events on this thread to ``node_id`` for
    the duration (overrides task-derived attribution)."""
    prev = getattr(_tls, "node_id", None)
    _tls.node_id = node_id
    try:
        yield
    finally:
        _tls.node_id = prev


def _current_override() -> str | None:
    """The explicit override alone (for context capture across pools)."""
    return getattr(_tls, "node_id", None)
