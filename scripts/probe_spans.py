#!/usr/bin/env python3
"""Three facts about the tracing of this program that only the machine
it runs on can give; prints one JSON object.

    python3 scripts/probe_spans.py          (on the chip: chiprun -- ...)

1. ``span_ns``: what one span costs on this host with nobody tracing —
   a span of the closed table (ring record + TraceAnnotation) as the
   outermost of its thread and nested in another, a device seam span
   that opens and closes a launch, a span outside the table; and
   ``parts_ns``: what it is made of.
2. ``scope_in``: where a ``jax.named_scope`` shows in a profiler trace
   of the device: in an op event's name, in one of the stats that
   ``jax.profiler.ProfileData`` gives for it, or only somewhere in the
   ``.xplane.pb`` (its event metadata, which ``ProfileData`` leaves out).
3. ``cache``: whether the persistent compile cache tells a program with
   scopes from the same program without (its key leaves metadata out):
   a program loaded from a cache that another build wrote carries that
   build's scopes, not this one's.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def span_ns(n: int = 200_000) -> dict:
    from elasticsearch_tpu.observability import tracing

    def loop(make) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        return round((time.perf_counter() - t0) / n * 1e9, 1)

    out = {"outside_the_table": loop(lambda: tracing.span("probe")),
           # the outermost span of a thread also reads the thread's CPU
           # clock, twice
           "in_the_table_outermost": loop(lambda: tracing.span("fetch.hits"))}
    with tracing.span("action.shard_msearch"):
        out["in_the_table_nested"] = loop(
            lambda: tracing.span("fetch.hits"))
        out["device_seam_launch_nested"] = loop(
            lambda: tracing.device_span("dispatch"))
        out["device_seam_upload_nested"] = loop(
            lambda: tracing.device_span("upload"))
    tracing.reset()
    return out


def parts_ns(n: int = 200_000) -> dict:
    """What a span is made of, one call each."""
    import struct
    import timeit

    from jax.profiler import TraceAnnotation
    rec, buf = struct.Struct("<8q"), bytearray(64 * 16)
    env = {"time": time, "TraceAnnotation": TraceAnnotation, "rec": rec,
           "buf": buf}
    stmts = {
        "monotonic_ns": "time.monotonic_ns()",
        "thread_time_ns": "time.thread_time_ns()",
        "perf_counter": "time.perf_counter()",
        "trace_annotation": "TraceAnnotation('es.x', request=5)"
                            ".__exit__(None, None, None)",
        "pack_into": "rec.pack_into(buf, 64, 1, 2, 3, 4, 5, 6, 7, 8)",
    }
    return {name: round(timeit.timeit(stmt, number=n, globals=env)
                        / n * 1e9, 1) for name, stmt in stmts.items()}


def scope_events(tdir: str, needle: str) -> dict:
    """Where ``needle`` shows: [plane, line, event name, stat], and what
    one op event of the device carries at all."""
    import jax
    hits, sample, raw = set(), None, False
    for path in glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True):
        with open(path, "rb") as f:
            raw = raw or needle.encode() in f.read()
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if needle in ev.name:
                        hits.add((plane.name, line.name, ev.name[:60],
                                  "<name>"))
                    stats = [(key, val.decode(errors="replace")
                              if isinstance(val, bytes) else val)
                             for key, val in ev.stats]
                    for key, val in stats:
                        if needle in str(val):
                            hits.add((plane.name, line.name, ev.name[:60],
                                      key))
                    if sample is None and line.name == "XLA Ops":
                        sample = {"plane": plane.name, "name": ev.name[:300],
                                  "stats": {k: str(v)[:200]
                                            for k, v in stats}}
    return {"found": sorted(hits)[:12], "anywhere_in_the_file": raw,
            "an_op_event": sample}


def traced(fn, *args) -> list:
    import jax
    with tempfile.TemporaryDirectory() as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            jax.block_until_ready(fn(*args))
        finally:
            jax.profiler.stop_trace()
        return scope_events(tdir, "probe_scope")


def main() -> int:
    out = {"span_ns": span_ns(), "parts_ns": parts_ns()}
    with tempfile.TemporaryDirectory() as cache:
        import jax
        import jax.numpy as jnp
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        dev = jax.devices()[0]
        out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        x = jnp.arange(1 << 20, dtype=jnp.float32).reshape(1 << 10, 1 << 10)

        def body(a):
            return jnp.tanh(a @ a.T).sum(axis=1)

        def plain(a):
            return body(a)

        def scoped(a):
            with jax.named_scope("probe_scope"):
                return body(a)

        def entries() -> int:
            return len([p for p in os.listdir(cache) if "atime" not in p])

        # the scoped build first, into an empty cache: its events show
        # where a scope lands in the trace
        jax.block_until_ready(jax.jit(scoped)(x))
        out["scope_in"] = traced(jax.jit(scoped), x)
        n1 = entries()
        # the same function without the scope, by the same name: a second
        # cache entry means the key saw the metadata
        plain.__name__ = plain.__qualname__ = "scoped"
        jax.clear_caches()
        jax.block_until_ready(jax.jit(plain)(x))
        n2 = entries()
        out["cache"] = {"entries_after_scoped": n1,
                        "entries_after_unscoped_twin": n2,
                        "key_ignores_scopes": n2 == n1,
                        "scopes_seen_running_the_unscoped_twin":
                            traced(jax.jit(plain), x)}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
