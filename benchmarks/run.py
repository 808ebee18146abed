"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Steps (benchmarks/README.md has the layout and how to add to it):

  1. raise the open-file limit to the hard one; fresh scratch directory
     inside the checkout (the WAL's fsyncs should hit the checkout's
     disk); free port;
  2. start the configuration's server as a child in its own session —
     `python -m raftsql_tpu.server.main <argv of the config file>`, or
     with `--trace 1` the same entry function under lib/serve_traced.py;
  3. wait until /healthz says ready, every group led, on the platform and
     chip count the configuration names, native WAL loaded;
  4. schema and load from --seed (every answer 204), then the cell's own
     mix as warm-up until no compile-cache miss has been seen for a while;
  5. scrape /metrics, measure for --seconds (generators in processes of
     their own, closed loop), scrape again; /metrics is never fetched
     inside the window;
  6. outside the window: read back and check the answers against the
     plain reference, SIGTERM the engine, reduce the trace;
  7. on every exit path kill the engine's process group and the
     generators and remove the scratch directory;
  8. last line of stdout: the result object; its last key, `compared`,
     holds each number the verdict compared beside its limit, and the
     same is the last line of stderr.  Everything else the run saw goes
     on earlier lines, prefixed `bench:` (benchmarks/README.md lists
     them; `slices` and `workers` say how the window went, 5 s by 5 s
     and HTTP worker by HTTP worker).

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics (benchmarks/layers/<name>.py each).
No result is printed, and the exit code is not 0, when the engine does
not come up on the device the cell needs.  This process never imports
JAX: the chip belongs to the engine.
"""
from __future__ import annotations

import argparse
from http.client import HTTPConnection
import importlib
import json
import math
import os
import resource
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import stats                                       # noqa: E402
from lib.engine import Engine, EngineFailure, free_port     # noqa: E402

T_START = time.monotonic()

WARM_MIN_S = 8.0            # the least warm-up of the cell's own mix
WARM_QUIET_S = 3.0          # ... and this long without a compile-cache miss
WARM_MAX_S = 90.0
TRACE_AFTER_S = 1.0         # into the window before the profiler starts
TRACE_SECONDS = 4.0
TRACE_DONE_S = 120.0        # for the profiler to write its file
GENERATOR_EXIT_S = 90.0     # for in-flight requests after the window
LIST_PHASE_S = 300.0
SCRAPE_CONNECTIONS = 4      # each lands on one of the SO_REUSEPORT workers
READBACK_SAMPLE = 1000
READBACK_CONNECTIONS = 128
READBACK_KEYS = 64          # keys of one group to a read-back statement
NOFILE_SPARE = 2048
SLICE_S = 5.0               # the `slices` line cuts the window this fine


class RunFailure(Exception):
    """A step of the run failed; no result is printed."""


def say(key: str, value) -> None:
    print(f"bench: {key}: {value}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str) -> dict:
    """The cell, its configuration file, its traffic file and the metrics
    it reports, found by name: BENCHMARK.json first, then
    benchmarks/rehearsal.json (cells that run on a CPU and are no part of
    the benchmark; such a cell says whose metrics it reports: `like`)."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    extra = load_json(os.path.join(HERE, "rehearsal.json"))
    cells = {w["name"]: w for w in manifest["workloads"] + extra["workloads"]}
    if name not in cells:
        raise RunFailure(f"no workload {name!r}; there are: "
                         + ", ".join(sorted(cells)))
    cell = cells[name]
    listed_as = cell.get("like", name)

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or listed_as in metric["workloads"]

    return {
        "cell": cell,
        "config": load_json(os.path.join(
            HERE, "configs", cell["config"] + ".json")),
        "traffic": load_json(os.path.join(
            HERE, "traffic", cell["traffic"] + ".json")),
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": [m for m in manifest["per_layer"] if mine(m)],
    }


def raise_nofile(clients: int) -> int:
    """Raise the soft open-file limit to the hard one and return it: the
    limit the engine inherits.  Required is only what this runner and its
    generators hold, a socket a client and NOFILE_SPARE.  The engine's
    store budgets its SQLite handles from the limit it inherits, and
    server/main.py refuses a limit under the store's own least with a
    sentence that names it."""
    need = clients + NOFILE_SPARE
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard != resource.RLIM_INFINITY and hard < need:
        raise RunFailure(f"open-file hard limit {hard} < {need} needed "
                         f"({clients} clients + {NOFILE_SPARE} spare)")
    want = hard if hard != resource.RLIM_INFINITY else max(soft, need)
    if soft < want:
        resource.setrlimit(resource.RLIMIT_NOFILE, (want, hard))
    return max(soft, want)


def fs_type(path: str) -> str:
    best, kind = "", "?"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mnt, typ = line.split()[:3]
                if path.startswith(mnt) and len(mnt) > len(best):
                    best, kind = mnt, typ
    except OSError:
        pass
    return kind


class Generators:
    """lib/loadgen.py processes of one phase."""

    def __init__(self, scratch: str):
        self.scratch = scratch
        self.procs: List[subprocess.Popen] = []
        self.outs: List[str] = []
        self._n = 0

    def spawn(self, spec: dict) -> None:
        self._n += 1
        base = os.path.join(self.scratch, f"gen{self._n}")
        spec = dict(spec, out=base + ".out.json")
        with open(base + ".spec.json", "w") as f:
            json.dump(spec, f)
        self.outs.append(spec["out"])
        self.procs.append(subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", "loadgen.py"),
             base + ".spec.json"],
            stdin=subprocess.PIPE, cwd=self.scratch))

    def spawn_mix(self, port: int, traffic: dict, p: dict, seed: int) -> None:
        """The traffic file's clients, split over its `processes`."""
        n = traffic["processes"]
        per = -(-traffic["clients"] // n)
        for first in range(0, traffic["clients"], per):
            self.spawn({"mode": "mix", "port": port, "ops": traffic["ops"],
                        "params": p, "seed": seed,
                        "clients": [first,
                                    min(per, traffic["clients"] - first)]})

    def tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write((line + "\n").encode())
            p.stdin.flush()

    def collect(self, timeout_s: float) -> List[dict]:
        deadline = time.monotonic() + timeout_s
        docs = []
        for p, out in zip(self.procs, self.outs):
            try:
                rc = p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                raise RunFailure("a load generator did not finish")
            if rc != 0:
                raise RunFailure(f"a load generator exited {rc}")
            p.stdin.close()
            docs.append(load_json(out))
        self.procs, self.outs = [], []
        return docs

    def destroy(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            p.stdin.close()
        self.procs = []


def run_list(gens: Generators, port: int, requests: List[list],
             connections: int, what: str) -> List[list]:
    """Send each request once; the answers in request order."""
    if not requests:
        return []
    t = time.monotonic()
    gens.spawn({"mode": "list", "port": port, "requests": requests,
                "connections": connections})
    answers = gens.collect(LIST_PHASE_S)[0]["answers"]
    say(what, f"{len(requests)} requests over "
        f"{min(connections, len(requests))} connections in "
        f"{time.monotonic() - t:.2f} s")
    return answers


def require_204(requests: List[list], answers: List[list], what: str) -> None:
    bad = [(r, a) for r, a in zip(requests, answers) if a[0] != 204]
    for r, a in bad[:5]:
        say(f"FAILED {what} request",
            f"group {r[1]} {r[2][:60]!r} -> {a[0]} {a[2][:160]!r}")
    if bad:
        raise RunFailure(f"{len(bad)} of {len(requests)} {what} statements "
                         f"were not acknowledged with 204")


class Scraper:
    """/metrics over SCRAPE_CONNECTIONS keep-alive connections opened
    before the window: each is pinned to one worker for its life, so the
    difference of a worker-local counter (the shm read counts, which a
    worker folds into the engine's document) between two scrapes of ONE
    connection is that worker's own."""

    def __init__(self, engine: Engine):
        self.engine = engine
        self.conns = [HTTPConnection("127.0.0.1", engine.port, timeout=30.0)
                      for _ in range(SCRAPE_CONNECTIONS)]

    def scrape(self) -> dict:
        docs = [self.engine.get_doc("/metrics", c) for c in self.conns]
        return {"t": time.monotonic(), "engine": docs[0], "workers": docs}

    def destroy(self) -> None:
        for c in self.conns:
            c.close()


def warm_up(engine: Engine) -> dict:
    """Let the cell's own mix run until no program has been compiled for
    WARM_QUIET_S (and at least WARM_MIN_S in all)."""
    began = quiet_since = time.monotonic()
    misses = None
    while True:
        time.sleep(1.0)
        cache = engine.get_doc("/metrics")["device"]["compile_cache"]
        now = time.monotonic()
        if cache["misses"] != misses:
            misses, quiet_since = cache["misses"], \
                (quiet_since if misses is None else now)
        if now - began >= WARM_MIN_S and now - quiet_since >= WARM_QUIET_S:
            return cache
        if now - began > WARM_MAX_S:
            raise RunFailure("still compiling after "
                             f"{WARM_MAX_S:.0f} s of warm-up")


def client_numbers(log: List[list], t0: float, t1: float) -> dict:
    """What the clients saw of the requests ANSWERED inside [t0, t1]."""
    window = [r for r in log if t0 <= r[6] <= t1]
    ok_w = [(r[6] - r[5]) * 1e3 for r in window
            if r[1] == "w" and r[7] == 204]
    ok_r = [(r[6] - r[5]) * 1e3 for r in window
            if r[1] == "r" and r[7] == 200]
    out = {
        "attempted": len(window),
        "failed": len(window) - len(ok_w) - len(ok_r),
        "writes": len(ok_w), "reads": len(ok_r),
        "ops_per_s": (len(ok_w) + len(ok_r)) / (t1 - t0),
        "write_p50_ms": stats.percentile(ok_w, 0.50),
        "write_p95_ms": stats.percentile(ok_w, 0.95),
        "read_p50_ms": stats.percentile(ok_r, 0.50),
        "read_p95_ms": stats.percentile(ok_r, 0.95),
    }
    statuses: Dict[int, int] = {}
    for r in window:
        statuses[r[7]] = statuses.get(r[7], 0) + 1
    out["statuses"] = statuses
    return out


def window_slices(docs: List[dict], t0: float, t1: float) -> dict:
    """The window cut into slices of SLICE_S seconds (the last one takes
    what is left): per slice [requests answered, median of the writes
    answered 204 in ms or None], over all clients (`all`) and per
    generator process (`by_process`, in `docs`' order).  A request falls
    into the slice it was ANSWERED in, as in client_numbers, so a row's
    counts sum to `attempted`.  A run that changes regime inside its
    window shows as slices that differ; a generator process that lags
    shows as one row of `by_process` that differs from the others.  `t0`
    is the window's start on CLOCK_MONOTONIC, the clock of the engine's
    own log on this machine."""
    n = max(1, math.ceil(round((t1 - t0) / SLICE_S, 6)))    # 40.0000001 s: 8

    def cut(log: List[list]) -> List[list]:
        cells: List[List[list]] = [[] for _ in range(n)]
        for r in log:
            if t0 <= r[6] <= t1:
                cells[min(int((r[6] - t0) // SLICE_S), n - 1)].append(r)
        out = []
        for rows in cells:
            ok_w = [(r[6] - r[5]) * 1e3 for r in rows
                    if r[1] == "w" and r[7] == 204]
            out.append([len(rows), round(statistics.median(ok_w), 1)
                        if ok_w else None])
        return out

    return {"slice_s": SLICE_S, "t0": round(t0, 3),
            "all": cut([r for d in docs for r in d["ops"]]),
            "by_process": [cut(d["ops"]) for d in docs]}


def worker_shares(before: dict, after: dict) -> List[list]:
    """Per scrape connection [writes its worker answered in the window,
    their mean ring round trip in ms]: a connection stays on one of the
    SO_REUSEPORT workers, so rows that agree are one worker's and rows
    that differ say how the kernel split the clients between them.  None
    where the program counts no `worker_stages.put.ring_rtt`."""
    path = "worker_stages.put.ring_rtt."
    return [[stats.delta(b, a, path + "n"),
             stats.per(b, a, path + "total_ms", path + "n")]
            for b, a in zip(before["workers"], after["workers"])]


def read_back(gens: Generators, port: int, ops, p: dict, seed: int,
              log: List[list]) -> Dict[str, Dict[str, str]]:
    """After the last write was answered: every key written and a seeded
    sample of the others, read `linear` and `follower` at the highest
    watermark any client saw for the key's group, READBACK_KEYS keys of
    one group to a statement.  {key: {mode: the key's row as the server
    rendered it, "" for no row}}."""
    marks: Dict[int, int] = {}
    by_group: Dict[int, List[str]] = {}
    for r in log:
        g = ops.group_of(p, r[2])
        if r[8] > marks.get(g, 0):
            marks[g] = r[8]
    for key in sorted({r[2] for r in log if r[1] == "w"}
                      | set(ops.sample_keys(p, seed, READBACK_SAMPLE))):
        by_group.setdefault(ops.group_of(p, key), []).append(key)
    requests, asked = [], []
    for g, keys in sorted(by_group.items()):
        for i in range(0, len(keys), READBACK_KEYS):
            chunk = keys[i:i + READBACK_KEYS]
            for mode, extra in (
                    ("linear", "X-Consistency: linear\r\n"),
                    ("follower", "X-Consistency: follower\r\n"
                     f"X-Raft-Session: {marks.get(g, 0)}\r\n")):
                requests.append(["GET", g, ops.read_many_sql(chunk), extra])
                asked.append((mode, chunk))
    answers = run_list(gens, port, requests, READBACK_CONNECTIONS,
                       "read-back")
    out: Dict[str, Dict[str, str]] = {}
    for (mode, chunk), (status, _wm, body, _ts, _ta) in zip(asked, answers):
        rows = {line.split("|")[1]: line + "\n"
                for line in body.splitlines()} if status == 200 else {}
        for key in chunk:
            out.setdefault(key, {})[mode] = rows.get(key, "") \
                if status == 200 else f"<status {status}: {body[:80]}>"
    return out


def reduce_trace(trace_dir: str) -> Optional[dict]:
    """lib/trace_reduce.py in a process of its own, pinned to the CPU
    (the engine has exited; this must never take or wait for a chip)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "trace_reduce.py"),
         trace_dir], env=env, stdout=subprocess.PIPE, text=True,
        timeout=300.0)
    if r.returncode != 0:
        say("trace", f"reduction exited {r.returncode}")
        return None
    return json.loads(r.stdout.strip().splitlines()[-1]) or None


def layer_metrics(found: dict, before: dict, after: dict, client: dict,
                  trace: Optional[dict]) -> dict:
    out = {}
    for m in found["per_layer"]:
        reader = importlib.import_module("layers." + m["name"])
        value = reader.read(before, after, client, trace)
        if value is None:
            say("left out", f"{m['name']}: its reader found nothing to read")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, found: dict, scratch: str, live: dict) -> int:
    config, traffic = found["config"], found["traffic"]
    p = dict(config["scale"], **traffic)
    ops = importlib.import_module("ops." + traffic["ops"])
    checker = importlib.import_module("ops." + traffic["checker"])
    peaks = load_json(os.path.join(HERE, "lib", "peaks.json"))["peaks"]
    traced = args.trace == 1
    nofile = raise_nofile(traffic["clients"])
    say("cell", f"{found['cell']['name']} = {config['name']} x "
        f"{traffic['name']}, seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}")
    say("scratch", f"{scratch} ({fs_type(scratch)})")

    trace_dir = os.path.join(scratch, "trace")
    os.makedirs(trace_dir)
    launcher = [os.path.join(HERE, "lib", "serve_traced.py"), trace_dir,
                str(TRACE_SECONDS)] if traced else \
        ["-m", "raftsql_tpu.server.main"]
    engine = live["engine"] = Engine(ROOT, launcher, config["argv"],
                                     config["env"], scratch, free_port())
    gens = live["gens"] = Generators(scratch)
    # While the engine boots: the statements of the set-up, from the seed.
    schema = [["PUT", g, sql, ""] for g, sql in ops.schema(p)]
    load = [["PUT", g, sql, ""] for g, sql in ops.load(p, args.seed)]
    health = engine.wait_ready(config["groups"], config["platform"],
                               found["cell"]["chips"])
    device = health["device"]
    if device["device_kind"] not in peaks:
        raise RunFailure(f"device kind {device['device_kind']!r} is not in "
                         f"lib/peaks.json")
    say("device", json.dumps({k: device[k] for k in
                              ("platform", "device_kind", "count", "jax")}))
    say("boot", f"/healthz up {health['healthz_up_s']} s, all "
        f"{config['groups']} groups led {health['all_led_s']} s after spawn; "
        f"compile cache {json.dumps(device['compile_cache'])}; open-file "
        f"limit {nofile}")

    conns = p["load_connections"]
    require_204(schema, run_list(gens, engine.port, schema, conns, "schema"),
                "schema")
    require_204(load, run_list(gens, engine.port, load, conns, "load"),
                "load")

    gens.spawn_mix(engine.port, traffic, p, args.seed)
    cache = warm_up(engine)
    scraper = live["scraper"] = Scraper(engine)
    before = scraper.scrape()
    t0 = time.monotonic() + 0.25
    t1 = t0 + args.seconds
    gens.tell(f"window {t0!r} {t1!r}")
    setup_s = t0 - T_START
    say("window", f"opens {setup_s:.2f} s after the runner started "
        f"(warm-up compile cache {json.dumps(cache)})")
    if traced:
        time.sleep(max(0.0, t0 + TRACE_AFTER_S - time.monotonic()))
        open(os.path.join(trace_dir, "start"), "w").close()
    time.sleep(max(0.0, t1 - time.monotonic()))
    after = scraper.scrape()
    scraper.destroy()
    docs = gens.collect(GENERATOR_EXIT_S)
    log = [r for d in docs for r in d["ops"]]
    client = client_numbers(log, t0, t1)
    client["window_s"] = t1 - t0
    client["setup_s"] = setup_s
    client["generator_cpu_s"] = [
        d["cpu"]["t1"][0] - d["cpu"]["t0"][0] for d in docs
        if "t0" in d["cpu"] and "t1" in d["cpu"]]
    say("clients", json.dumps({k: client[k] for k in (
        "attempted", "failed", "writes", "reads", "statuses", "ops_per_s",
        "write_p50_ms", "write_p95_ms", "read_p50_ms", "read_p95_ms",
        "generator_cpu_s")}))
    say("slices", json.dumps(window_slices(docs, t0, t1)))
    say("workers", json.dumps(worker_shares(before, after)))
    compiles = stats.delta(before["engine"], after["engine"],
                           "device.compile_cache.misses")
    say("compiled inside the window", compiles)

    trace_times = None
    if traced:
        done = os.path.join(trace_dir, "done")
        give_up = time.monotonic() + TRACE_DONE_S
        while not os.path.exists(done) and time.monotonic() < give_up:
            time.sleep(0.2)
        if os.path.exists(done):
            trace_times = load_json(done)
    readback = read_back(gens, engine.port, ops, p, args.seed, log)
    rc = engine.stop()
    say("engine exit code on SIGTERM", rc)
    engine.destroy()

    trace = None
    if trace_times is not None:
        trace = reduce_trace(trace_dir)
        if trace is not None:
            trace.update(trace_times)
            say("trace", json.dumps(trace))
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        shutil.copy(engine.log_path, args.keep)
        if traced:
            shutil.copytree(trace_dir, os.path.join(args.keep, "trace"),
                            dirs_exist_ok=True)

    t = time.monotonic()
    verdict = checker.check(
        ops, p, args.seed, [(r[1], r[2]) for r in schema + load], log,
        readback, traffic["read_consistency"])
    say("check", json.dumps(dict(verdict, seconds=round(
        time.monotonic() - t, 2))))

    peak = max([b for b in after["engine"]["device"]["peak_bytes_in_use"]
                if b is not None], default=0)
    dev = {"platform": device["platform"], "kind": device["device_kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    if traced:
        if trace is None:
            raise RunFailure("the traced run produced no device trace")
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        metrics = layer_metrics(found, before, after, client, trace)
    else:
        metrics = {}
        for m in found["end_to_end"]:
            value = client.get(m["name"])
            if value is None:
                say("left out", f"{m['name']}: fewer than {stats.TAIL} "
                    f"samples beyond it in the window")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": verdict["correct"], "attempted": client["attempted"],
              "failed": client["failed"], "metrics": metrics, "device": dev}
    if traced:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    # What `correct` compared, each beside its limit: the comparison with
    # the plain reference is exact, so the limit is 0.
    result["compared"] = {"mismatched": {"value": verdict["mismatched"],
                                         "limit": 0}}
    live["result"] = result
    return 0 if verdict["correct"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default="",
                    help="copy the engine's log and the trace here "
                         "(for looking by hand)")
    args = ap.parse_args(argv)

    def interrupted(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    signal.signal(signal.SIGTERM, interrupted)
    signal.signal(signal.SIGINT, interrupted)
    live: dict = {}
    scratch = None
    rc = 1
    try:
        found = find_cell(args.workload)
        scratch = tempfile.mkdtemp(prefix="raftsql-bench-", dir=ROOT)
        rc = run(args, found, scratch, live)
    except (RunFailure, EngineFailure, KeyboardInterrupt, OSError,
            ValueError, KeyError) as e:
        print(f"bench: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        if "engine" in live:
            print("bench: end of the engine's log:\n"
                  + live["engine"].log_tail(), file=sys.stderr)
        rc = 1
    finally:
        for name in ("scraper", "gens", "engine"):
            if name in live:
                try:
                    live[name].destroy()
                except Exception as e:                  # noqa: BLE001
                    print(f"bench: cleanup of {name}: {e!r}",
                          file=sys.stderr)
        if scratch is not None:
            shutil.rmtree(scratch, ignore_errors=True)
    if "result" in live:
        for name, c in live["result"]["compared"].items():
            print(f"bench: compared: {name} {c['value']} (limit "
                  f"{c['limit']})", file=sys.stderr, flush=True)
        print(json.dumps(live["result"]), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
