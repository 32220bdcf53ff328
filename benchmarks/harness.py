"""One run of one cell: load, warm, measure, compare, print one line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own that is found by the name
``BENCHMARK.json`` gives it; nothing in this file names a cell, a
configuration or a metric.

    BENCHMARK.json  workloads[].{name, config, traffic}, configs[].file,
                    end_to_end[], per_layer[]
    configs/<config>.json       sizes, index mapping, corpus.kind
    corpora/<corpus.kind>.py    generator, installer, reference, compare
    traffic/<traffic>.json      one traffic mix: its kind and parameters
    traffic/<kind>.py           the generator of that kind: jobs for the
                                load generators, warm requests, what to check
    workloads/<cell>.json       expected lanes, traced slice, sample, limits
    end_metrics/<metric>.py     read(ctx) -> number | None   (--trace 0)
    layer_metrics/<metric>.py   read(ctx) -> number | None   (--trace 1)

This file knows nothing of what a query or a document is (the data kind
does) nor of how requests arrive (the traffic kind does): it takes jobs,
warm requests and the queries to check, and drives them.
"""

from __future__ import annotations

import contextlib
import gc
import http.client
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks import loadgen, stats, trace_reduce

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
BAD_REASONS = ("device-error", "device-stall", "plan-error")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_start_monotonic() -> float:
    """When this process started, on ``time.monotonic()``'s clock (both
    count from boot on Linux)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return ticks / os.sysconf("SC_CLK_TCK")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """The files of one cell, found by name."""

    def __init__(self, name: str, rehearsal: bool = False,
                 bench_file: Path | None = None,
                 data_root: Path = HERE):
        """``bench_file`` and ``data_root`` let the self-checks drive
        cells of their own (``tests/data``) through the same code."""
        self.bench = json.loads(
            (bench_file or REPO / "BENCHMARK.json").read_text())
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"no workload [{name}] in BENCHMARK.json")
        self.name, self.chips = name, int(entry["chips"])
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == entry["config"])
        self.config = json.loads((REPO / cfg["file"]).read_text())
        if rehearsal:
            # the tiny size the file itself gives for a CPU rehearsal
            for key, over in self.config.get("rehearsal", {}).items():
                self.config[key] = {**self.config[key], **over}
        self.traffic = json.loads(
            (data_root / "traffic" / f"{entry['traffic']}.json").read_text())
        self.spec = json.loads(
            (data_root / "workloads" / f"{name}.json").read_text())
        self.corpus_mod = load_module(
            HERE / "corpora" / f"{self.config['corpus']['kind']}.py",
            f"bench_corpus_{self.config['corpus']['kind']}")
        self.traffic_mod = load_module(
            HERE / "traffic" / f"{self.traffic['kind']}.py",
            f"bench_traffic_{self.traffic['kind']}")

    def metrics(self, group: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]


class Http:
    """The harness's own client (set-up and warm-up, never the window)."""

    def __init__(self, host: str, port: int):
        self.conn = http.client.HTTPConnection(host, port, timeout=1200)

    def call(self, method: str, path: str, body=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        self.conn.request(method, path, body=body, headers={
            "Content-Type": "application/json"} if body else {})
        resp = self.conn.getresponse()
        raw = resp.read()
        if resp.status >= 300:
            raise RuntimeError(f"{method} {path} → HTTP {resp.status}: "
                               f"{raw[:400]!r}")
        ctype = resp.getheader("Content-Type") or ""
        return json.loads(raw) if "json" in ctype else raw.decode()


@contextlib.contextmanager
def served_node(workdir: str, settings: dict):
    """A ``Node`` and a ``RestServer`` as ``bootstrap.main`` starts them:
    the configuration's node settings over the defaults, HTTP ingress
    last, port 0."""
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest.server import RestServer
    data = os.path.join(workdir, "data")
    node = Node(Settings({**settings, "path.data": data}),
                data_path=data).start()
    server = RestServer(node, host="127.0.0.1", port=0).start()
    try:
        yield node, server
    finally:
        server.stop()
        node.close()


# ---------------------------------------------------------------------------
# the books: did the chip path do the work?
# ---------------------------------------------------------------------------

def counters(node) -> dict:
    from elasticsearch_tpu.observability import costs
    from elasticsearch_tpu.search import jit_exec
    st = jit_exec.cache_stats()
    lanes: dict = {}
    for nid in (costs.node_ids() or [""]):
        for lane, ent in costs.lane_rollup(nid).items():
            agg = lanes.setdefault(lane, {"dispatches": 0, "compiles": 0})
            agg["dispatches"] += ent["dispatches"]
            agg["compiles"] += ent["compiles"]
    return {"jit": st, "lanes": lanes,
            "scheduler": node.search_actions.scheduler.stats(),
            "cpu_s": time.process_time(),
            "gc": [g["collections"] for g in gc.get_stats()]}


def book_checks(before: dict, after: dict, expected_lanes: list) -> dict:
    """→ name → [count, limit]; every limit is 0 (or, for a lane that
    has to dispatch, a floor of 1 written as missing = 0)."""
    jb, ja = before["jit"], after["jit"]
    bad = 0
    for name, book in ja.items():
        if name.endswith("_reasons") and isinstance(book, dict):
            old = jb.get(name, {})
            bad += sum(n - old.get(r, 0) for r, n in book.items()
                       if r in BAD_REASONS)
    br = ja["plane_breaker"]
    compiles = sum(v["compiles"] for v in after["lanes"].values()) \
        - sum(v["compiles"] for v in before["lanes"].values())
    misses = sum(ja[k] - jb.get(k, 0) for k in ja
                 if k == "misses" or k.endswith("_program_misses"))
    missing = [ln for ln in expected_lanes
               if after["lanes"].get(ln, {}).get("dispatches", 0)
               - before["lanes"].get(ln, {}).get("dispatches", 0) <= 0]
    log("dispatches in the window by lane: " + json.dumps({
        ln: v["dispatches"] - before["lanes"].get(ln, {}).get(
            "dispatches", 0) for ln, v in after["lanes"].items()}))
    return {
        "eager_fallbacks": [ja["fallbacks"] - jb["fallbacks"], 0],
        "bad_fallback_reasons": [bad, 0],
        "breaker_trips": [br["trips"] + br["errors_total"]
                          + int(br["state"] != "closed"), 0],
        "watchdog_stalls": [ja["watchdog_stalls"] - jb["watchdog_stalls"]
                            + ja["watchdog_quarantines"]
                            - jb["watchdog_quarantines"], 0],
        "compiles_in_window": [max(compiles, misses), 0],
        "lanes_missing": [len(missing), 0],
        "scheduler_unreconciled":
            [int(not after["scheduler"]["reconciled"]), 0],
    }


# ---------------------------------------------------------------------------
# the load generators
# ---------------------------------------------------------------------------

def sample_ids(cell: Cell, plan: dict, seed: int) -> list:
    """Which requests' replies are kept for the comparison: drawn from
    the seed among those the traffic kind says surely run."""
    rng = np.random.default_rng([seed, 11])
    n_check, ids = int(cell.spec["check_requests"]), plan["sure"]
    take = rng.choice(len(ids), size=min(3 * n_check, len(ids)),
                      replace=False)
    return [ids[i] for i in sorted(take)]


def start_generators(jobs: list, common: dict, workdir: str,
                     tag: str) -> list:
    """Start one generator process per job and wait until each has its
    connections open and warm → [(process, out file)]."""
    go = os.path.join(workdir, f"go_{tag}.json")
    procs = []
    for i, job in enumerate(jobs):
        path = os.path.join(workdir, f"job_{tag}_{i}.json")
        out = os.path.join(workdir, f"out_{tag}_{i}.json")
        with open(path, "w") as f:
            json.dump({**job, **common, "out": out, "go": go}, f)
        procs.append((subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), path]), out))
    deadline = time.monotonic() + 300
    while not all(os.path.exists(out + ".ready") for _, out in procs):
        if time.monotonic() > deadline or any(
                p.poll() is not None for p, _ in procs):
            for p, _ in procs:
                p.kill()
                p.wait()
            raise RuntimeError("a load generator did not get ready")
        time.sleep(0.01)
    return procs


def release_generators(procs: list, workdir: str, tag: str,
                       seconds: float) -> tuple:
    """Tell the generators when the window is → (t_start, t_end)."""
    t_start = time.monotonic() + 0.25
    go = os.path.join(workdir, f"go_{tag}.json")
    with open(go + ".tmp", "w") as f:
        json.dump({"t_start": t_start, "t_end": t_start + seconds}, f)
    os.replace(go + ".tmp", go)
    return t_start, t_start + seconds


def collect_generators(procs: list, deadline_s: float) -> tuple:
    records, saved = [], {}
    try:
        for proc, out in procs:
            proc.wait(timeout=max(1.0, deadline_s - time.monotonic()))
            if proc.returncode != 0:
                raise RuntimeError(f"load generator exited "
                                   f"{proc.returncode}")
            with open(out) as f:
                got = json.load(f)
            records += got["records"]
            saved.update(got["saved"])
    finally:
        for proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return records, saved


# ---------------------------------------------------------------------------
# warm-up: this cell's shapes and no others
# ---------------------------------------------------------------------------

def warm_up(http: Http, requests: list) -> None:
    """The requests the traffic and data kinds say reach every program
    the window can reach, through the served path. A reply with a failed
    item (the coordinator abandons a shard that compiles for longer than
    its stall ceiling; the compile goes on behind it) is asked again."""
    for i, req in enumerate(requests):
        for attempt in range(4):
            t0 = time.perf_counter()
            raw = json.dumps(http.call("POST", req["path"],
                                       req["body"])).encode()
            good = loadgen.items_ok(200, raw, req["items"])
            log(f"warm-up {i + 1}/{len(requests)}: {good} of "
                f"{req['items']} item(s) on {req['path']} answered in "
                f"{time.perf_counter() - t0:.2f} s")
            if good == req["items"]:
                break
        else:
            raise RuntimeError(f"warm request {i + 1} still answers with "
                               f"failures: {raw[:400]!r}")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def hbm_bytes(http: Http, index: str) -> int:
    text = http.call("GET", "/_cat/hbm?h=index,device,bytes")
    return sum(int(line.split()[2]) for line in text.splitlines()
               if line.split()[0] == index)


@contextlib.contextmanager
def prepared(cell: Cell, seed: int, phases: dict):
    """Set-up: the corpus from the seed, a served node holding it, the
    device columns uploaded → an environment :func:`window` can measure
    in, more than once (the knee sweep does)."""
    import jax
    from elasticsearch_tpu.common.device import ensure_compile_cache
    # every program goes to the persistent cache, also the quick ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {ensure_compile_cache()}")
    workdir = tempfile.mkdtemp(prefix="bench_")
    index = cell.config["index_name"]
    mod = cell.corpus_mod

    def phase(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        phases[name] = round(phases.get(name, 0.0)
                             + time.perf_counter() - t0, 3)
        log(f"phase {name}: {time.perf_counter() - t0:.2f} s")
        return out

    try:
        corpus = phase("corpus", mod.generate, cell.config, seed, log)
        with served_node(workdir, cell.config.get("node_settings", {})) \
                as (node, server):
            http = Http(server.host, server.port)
            http.call("PUT", f"/{index}", mod.mapping(cell.config))
            health = http.call("GET", f"/_cluster/health/{index}"
                               "?wait_for_status=green&timeout=60s")
            if health["status"] != "green":
                raise RuntimeError(f"[{index}] not green: {health}")
            phase("install", mod.install, corpus, node, index, log)
            http.call("POST", f"/{index}/_refresh")
            count = http.call("GET", f"/{index}/_count")["count"]
            if count != corpus["n_docs"]:
                raise RuntimeError(f"[{index}] holds {count} docs, "
                                   f"expected {corpus['n_docs']}")
            yield {"cell": cell, "seed": seed, "corpus": corpus,
                   "node": node, "server": server, "http": http,
                   "index": index, "workdir": workdir, "phase": phase,
                   "warmed": False}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def window(env: dict, seconds: float, trace: bool) -> dict:
    """Warm what is not warm yet, then one measured window → what the
    metrics and the comparison read."""
    import jax
    cell, node, http = env["cell"], env["node"], env["http"]
    index, workdir, phase = env["index"], env["workdir"], env["phase"]
    server, seed = env["server"], env["seed"]
    tag = f"w{env.setdefault('windows', 0)}"
    env["windows"] += 1
    plan = phase("traffic", cell.traffic_mod.build, cell.traffic,
                 cell.corpus_mod, env["corpus"], seed, seconds, index,
                 node.search_actions.scheduler.max_batch)
    keep = sample_ids(cell, plan, seed)
    common = {"host": server.host, "port": server.port,
              "timeout_s": seconds + 60.0, "save": keep}
    if not env["warmed"]:
        phase("upload+first", warm_up, http, plan["warm"][:1])
        phase("warm-up", warm_up, http, plan["warm"][1:])
        # a short rehearsal of the window's own traffic: whatever the
        # enumerated warm-up missed compiles here, not in the window
        ws = float(cell.traffic.get("warm_seconds", 2.0))

        def warm_window():
            for attempt in range(2):
                tag_w = f"warm{attempt}"
                procs = start_generators(
                    plan["jobs"], {**common, "save": []}, workdir, tag_w)
                _, end = release_generators(procs, workdir, tag_w, ws)
                recs, _ = collect_generators(procs, end + seconds + 75)
                bad = [r for r in recs if r[5] != r[6]]
                if not bad:
                    return
                log(f"warm window: {len(bad)} request(s) answered with "
                    f"failures (HTTP {sorted({r[4] for r in bad})})")
            raise RuntimeError("the warm window still meets failures")
        phase("warm-window", warm_window)
        env["warmed"] = True
    procs = start_generators(plan["jobs"], common, workdir, tag)
    try:
        gc.collect()
        before = counters(node)
        t_start, t_end = release_generators(procs, workdir, tag, seconds)
        setup_s = t_start - process_start_monotonic()
        traced = traced_slice(
            node, workdir, t_start, seconds,
            float(cell.spec.get("trace_seconds", 4.0))) if trace else None
        time.sleep(max(0.0, t_end - time.monotonic()))
    except BaseException:
        for proc, _ in procs:
            proc.kill()
            proc.wait()
        raise
    records, saved = collect_generators(procs, t_end + seconds + 75)
    after = counters(node)
    peak = int((jax.local_devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))
    return {"cell": cell, "saved": saved, "keep": keep, "plan": plan,
            # the streams the end-to-end metrics count; the others only
            # load the system
            "records": [r for r in records
                        if r[0] not in plan["unmeasured"]],
            "all_records": records,
            "corpus_stats": cell.corpus_mod.stats(env["corpus"]),
            "t_start": t_start, "t_end": t_end,
            "setup_s": setup_s, "before": before, "after": after,
            "traced": traced, "peak": peak,
            "resident_bytes": hbm_bytes(http, index)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             dev: dict, rehearsal: bool = False) -> dict:
    """One whole run → the result line as a dict."""
    import jax
    phases: dict = {}
    with prepared(cell, seed, phases) as env:
        ctx = window(env, seconds, trace)
        corpus = env["corpus"]
    del env
    # the program's state is freed and the peak is read: now the reference
    gc.collect()
    jax.clear_caches()
    numbers = book_checks(ctx["before"], ctx["after"],
                          cell.spec["expected_lanes"])
    t0 = time.perf_counter()
    compared = compare_sample(cell, corpus, ctx["plan"], ctx["saved"],
                              ctx["keep"], ctx["all_records"])
    observed = {k: v for k, (v, lim) in compared.items() if lim is None}
    numbers.update({k: v for k, v in compared.items()
                    if v[1] is not None})
    phases["reference"] = round(time.perf_counter() - t0, 3)
    log(f"phase reference: {phases['reference']:.2f} s")
    attempted, failed = stats.attempted_failed(
        ctx["records"], ctx["t_start"], ctx["t_end"])
    # a refusal (429) is a failed request: it is counted in ``failed`` and
    # weighs on the tail as the window's length. A request whose answer
    # never came (no reply, a 5xx, a reply cut short) is for ``correct``
    lost = sum(r[6] - r[5] for r in ctx["all_records"]
               if r[5] != r[6] and r[4] != 429
               and ctx["t_start"] <= r[1] < ctx["t_end"])
    numbers["answers_never_came"] = [int(lost), 0]
    slow_seconds(ctx)
    window_log(ctx)
    b, a = ctx["before"], ctx["after"]
    log(f"server process in the window: {a['cpu_s'] - b['cpu_s']:.2f} s of "
        f"CPU, collections by generation "
        f"{[y - x for x, y in zip(b['gc'], a['gc'])]}")
    for r in [r for r in ctx["all_records"] if r[5] != r[6]][:20]:
        log(f"failed request {r[0]}: due at {r[1] - ctx['t_start']:.2f} s, "
            f"HTTP {r[4]}, {r[5]} of {r[6]} answered after "
            f"{r[3] - r[1]:.2f} s")
    correct = all(v <= lim for v, lim in numbers.values())
    ctx["dev"] = dev
    device = {**dev, "memory_peak_bytes": ctx["peak"]}
    line = {"correct": bool(correct) and not rehearsal,
            "attempted": attempted, "failed": failed,
            "metrics": read_metrics(cell, ctx, trace), "device": device}
    if rehearsal:
        line["rehearsal"] = True
    traced = ctx["traced"]
    if traced and traced.get("reduced"):
        red = traced["reduced"]
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
        log("device seconds by XLA module in the traced slice: "
            + json.dumps(red["modules"]))
    line["phases_s"] = phases
    line["resident_bytes"] = ctx["resident_bytes"]
    line["observed"] = observed
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    return line


def slow_seconds(ctx: dict) -> None:
    """For the reader of a run's log: the window's worst seconds by the
    slowest request due in each (a stall shows as a run of them)."""
    worst: dict = {}
    for _id, due, _sent, done, _st, _ok, _items in ctx["records"]:
        sec = int(due - ctx["t_start"])
        worst[sec] = max(worst.get(sec, 0.0), done - due)
    top = sorted(worst.items(), key=lambda kv: -kv[1])[:8]
    log("slowest request due in second: " + ", ".join(
        f"{sec}: {lat * 1e3:.0f} ms" for sec, lat in sorted(top)))


def window_log(ctx: dict) -> dict:
    """What a run WAS, for whoever reads why a set of runs spread: the
    scheduler's counters over the window and its pace at both ends (at
    which staged depth it ran, how many batches it held and for how long,
    the rows a batch), and the served rate second by second. A run that
    sat at another depth reads level at another height; one that met a
    stall shows a few empty seconds in a level run; a rate that wanders
    inside every run shows as that. Read after the window from what
    :func:`window` took at its ends and from the generators' records:
    nothing runs inside the window for it. Logged on standard error only
    → what was logged, for the self-checks."""
    sched = stats.scheduler_window(ctx["before"]["scheduler"],
                                   ctx["after"]["scheduler"])
    log("scheduler in the window: " + ", ".join(
        f"{k} {sched[k]:.4g}" for k in (
            *stats.SCHEDULER_COUNTS, "held_share", "hold_ms_per_held",
            "rows_per_batch") if k in sched))
    for lane, ends in sched["pace"].items():
        log(f"scheduler pace [{lane}]: " + "; ".join(
            f"at the window's {end}: " + (", ".join(
                f"{k} {v}" for k, v in doc.items()) if doc else "nothing")
            for end, doc in ends.items()))
    by_second = stats.answers_by_second(ctx["records"], ctx["t_start"],
                                        ctx["t_end"])
    summary = stats.second_summary(by_second)
    log("answers in each second of the window: "
        + " ".join(f"{n:.0f}" for n in by_second))
    if summary:
        log(f"answers a second: least {summary['least']:.1f}, median "
            f"{summary['median']:.1f}, greatest {summary['greatest']:.1f}; "
            f"stalled seconds (under half the median): "
            f"{summary['stalled']}")
    return {"scheduler": sched, "answers_by_second": by_second,
            "seconds": summary}


def traced_slice(node, workdir: str, t_start: float, seconds: float,
                 length: float) -> dict:
    """Profile ``length`` steady seconds inside the window (half of a
    window that is shorter than twice that), with the counters read at
    both ends of the slice. Busy and idle time are taken on the trace's
    own clock, over the trace's own span; ``t0`` and ``t1`` are the
    slice on ``time.monotonic()``, the generators' clock."""
    import jax
    length = min(length, seconds / 2.0)
    time.sleep(max(0.0, t_start + min(2.0, seconds / 4.0)
                   - time.monotonic()))
    tdir = os.path.join(workdir, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    ta = time.monotonic()
    jax.profiler.start_trace(tdir, profiler_options=opts)
    c0, t0 = counters(node), time.monotonic()
    time.sleep(length)
    c1, t1 = counters(node), time.monotonic()
    jax.profiler.stop_trace()
    log(f"profiler: start took {t0 - ta:.2f} s, traced {t1 - t0:.2f} s, "
        f"stop took {time.monotonic() - t1:.2f} s")
    path = trace_reduce.find_xplane(tdir)
    reduced = trace_reduce.reduce_events(
        trace_reduce.device_events(path)) if path else None
    shutil.rmtree(tdir, ignore_errors=True)
    return {"reduced": reduced, "before": c0, "after": c1,
            "t0": t0, "t1": t1}


def compare_sample(cell: Cell, corpus: dict, plan: dict, saved: dict,
                   keep: list, records: list) -> dict:
    """The sampled replies of the timed window against the plain
    reference → name → [worst value, limit]."""
    mod, limits = cell.corpus_mod, cell.spec["limits"]
    n_check = int(cell.spec["check_requests"])
    have = [rid for rid in keep if str(rid) in saved][:n_check]
    worst: dict = {}
    # of the sampled requests that were sent and answered whole, as many
    # as the cell checks have to be there
    answered = {r[0] for r in records if r[5] == r[6]}
    due_back = min(n_check, sum(1 for rid in keep if rid in answered))
    out = {"answers_missing": [int(len(have) < max(due_back, 1)), 0]}
    checks = plan["checks"]
    queries = [q for rid in have for q in checks[rid]["queries"]]
    if queries:
        ref = mod.Reference(corpus, queries, log)
        for rid in have:
            reply = json.loads(saved[str(rid)])
            replies = reply["responses"] if "responses" in reply else [reply]
            for q, one in zip(checks[rid]["queries"], replies):
                got = mod.compare(ref.scores(q), checks[rid]["request"],
                                  *mod.parse_reply(one))
                for name in got:
                    worst[name] = max(worst.get(name, 0.0), got[name])
        log(f"compared {len(queries)} answers of {len(have)} requests")
    # a number the data kind counts but the cell gives no limit is an
    # observation: it is printed, and never decides ``correct``
    out.update({name: [worst.get(name, 0.0), limits.get(name)]
                for name in {**worst, **limits}})
    return out


def reader_file(folder: str, name: str) -> Path:
    """A metric's reader: ``<folder>/<name>.py``; the parts of one
    quantity that is split by the end-to-end metric it moves
    (``<quantity>.<part>``) share ``<folder>/<quantity>.py`` unless a part
    has a file of its own."""
    path = HERE / folder / f"{name}.py"
    if not path.exists() and "." in name:
        path = HERE / folder / f"{name.rsplit('.', 1)[0]}.py"
    return path


def read_metrics(cell: Cell, ctx: dict, trace: bool) -> dict:
    """``--trace 0``: the cell's end-to-end metrics. ``--trace 1``: its
    per-layer metrics, each from a reader of its own; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    group = "per_layer" if trace else "end_to_end"
    for m in cell.metrics(group):
        folder = "layer_metrics" if trace else "end_metrics"
        reader = load_module(reader_file(folder, m["name"]),
                             f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
