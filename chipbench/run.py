"""chipbench — the benchmark's one command.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the chip. It starts the Coordinator and both frontends
in-process with the calls `python -m materialize_tpu serve` makes, talks to
them over real sockets, and prints one JSON object as its last line. What a
cell is comes from data: `BENCHMARK.json` names the cell, its configuration
(`chipbench/configs/<config>.json`), its traffic mix
(`chipbench/workloads/<traffic>.json`) and its metrics
(`chipbench/metrics/<name>.json`, read by `chipbench/readers/<reader>.py`).
No cell, configuration or metric is named in this file.

Set-up (process start -> window start): the configuration's SQL over pgwire
on shipped defaults, a SUBSCRIBE client on a connection of its own, then the
warm-up refreshes the traffic mix asks for. Window: the mix's refreshes for
`--seconds` (or until its fixed number of them is done); it ends when the
last refresh started before the deadline has reached the subscriber, and
every rate divides by that real elapsed time. After the window, untimed: the
comparison with the plain reference that decides `correct`, then the trace
reduction.

`--rehearse` runs the same files at the configuration's rehearsal scale on
`JAX_PLATFORMS=cpu` and prints counts and `correct` only. Without it a run
off a TPU exits non-zero and prints no last line.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, to all intents: only the standard library is loaded yet

import argparse
import functools
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

from .clients import PgClient, Subscriber, http_sql
from .trace_reduce import reduce_trace
from .work import Recorder

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
PACKAGE = HERE.name


class BenchFailure(Exception):
    """The run cannot give a result (as opposed to giving `correct: false`)."""


def one(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise BenchFailure(f"{name!r} is named {len(found)} times in BENCHMARK.json")
    return found[0]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: str):
    """`package.module:attribute` -> the object."""
    mod, attr = spec.split(":")
    return getattr(importlib.import_module(mod), attr)


def annotate(kind: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(f"{PACKAGE}.{kind}")


def mark(kind: str) -> None:
    with annotate(kind):
        pass


# -- compile accounting (jax.monitoring; chip_smoke.py's, PR 25) ---------------


class Compiles:
    """XLA programs requested, persistent-cache hits among them, and the
    seconds spent in backend compile (or cache retrieval)."""

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return (self.programs, self.cache_hits, self.seconds)

    def since(self, snap: tuple) -> dict:
        p, h, s = self.programs - snap[0], self.cache_hits - snap[1], self.seconds - snap[2]
        return {"programs": p, "cache_hits": h, "compiled": p - h, "seconds": s}


# -- the served deployment (chip_smoke.py's `Served`) --------------------------


class Served:
    """Coordinator + both frontends, started as `cmd_serve` starts them."""

    def __init__(self):
        from materialize_tpu.adapter import Coordinator
        from materialize_tpu.frontend import serve
        from materialize_tpu.frontend.pgwire import serve_pgwire

        self.coord = Coordinator()
        self.httpd = serve(self.coord, host="127.0.0.1", port=0)
        self.lock = self.httpd.RequestHandlerClass.lock
        self.pg_srv, _thread = serve_pgwire(
            self.coord, host="127.0.0.1", port=0, lock=self.lock,
            reactor=getattr(self.httpd, "reactor", None),
        )
        self.http_port = self.httpd.server_address[1]
        self.pg_port = self.pg_srv.getsockname()[1]
        self.sql = PgClient(self.pg_port)

    def advance(self) -> int:
        """One source tick under the frontend lock, as `serve --advance-every` does it."""
        with self.lock:
            return self.coord.advance()

    def dataflow(self, view: str):
        gid = self.coord.catalog.get(view).global_id
        return next(df for g, df, _src in self.coord.dataflows if g == gid)

    def generator(self):
        return self.coord.generators[0][0]

    def close(self) -> None:
        self.sql.close()
        self.pg_srv.close()
        self.httpd.shutdown()


def device_leaves(root) -> list:
    """Every jax.Array reachable from `root` (a dataflow, a trace)."""
    import jax

    seen, out, stack = set(), [], [root]
    while stack:
        o = stack.pop()
        if id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, jax.Array):
            out.append(o)
        elif isinstance(o, dict):
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        elif hasattr(o, "__dict__") and not isinstance(o, type) and not callable(o):
            stack.extend(vars(o).values())
    return out


def render_histogram() -> tuple:
    """(sum in ns, count) of the program's `mzt_dataflow_tick_duration_ns`,
    over all dataflows: host wall of the render's steps."""
    from materialize_tpu.obs import metrics as obs_metrics

    total, count = 0.0, 0
    for fam in obs_metrics.REGISTRY.families():
        if fam.name == "mzt_dataflow_tick_duration_ns":
            for _labels, (_buckets, s, n) in fam.samples:
                total += s
                count += n
    return total, count


# -- the traffic: one general generator over the workload file's parameters ---


class Traffic:
    """Refreshes closed loop, as the workload file says: the next one is
    created once the last has reached the subscriber. Every one is recorded."""

    def __init__(self, served: Served, sub, spec: dict):
        self.served, self.sub, self.spec = served, sub, spec
        self.refreshes: list = []

    def refresh(self) -> dict:
        rec = {"created": time.perf_counter(), "ts": None, "arrival": None, "error": None}
        s0, n0 = render_histogram()
        with annotate("advance"):
            try:
                rec["ts"] = self.served.advance()
            except Exception as e:  # a refresh that raises is a failed request, not a failed run
                rec["error"] = repr(e)
        rec["done"] = time.perf_counter()
        s1, n1 = render_histogram()
        rec["render_ns"], rec["render_steps"] = s1 - s0, n1 - n0
        self.refreshes.append(rec)
        if rec["ts"] is not None:
            with annotate("deliver"):
                rec["arrival"] = self.sub.wait_past(rec["ts"], self.spec["deliver_timeout_s"])
        return rec

    def run(self, seconds: float) -> tuple:
        """Drives the window; returns (start, end) on the host clock."""
        start = time.perf_counter()
        deadline = start + seconds
        most = self.spec.get("refreshes")  # a fixed amount of work, where the file gives one
        while time.perf_counter() < deadline and (most is None or len(self.refreshes) < most):
            self.refresh()
        return start, max([r["arrival"] or r["done"] for r in self.refreshes] + [start])


# -- what decides `correct` -----------------------------------------------------


def holds(check: dict) -> bool:
    return 0 <= check["value"] <= check["limit"]


def checks_of(config: dict, live: dict, answers: dict, counted: dict) -> tuple:
    """Every number compared, beside its limit, and how many rows the
    reference has. `answers` maps a check's name to a function that gives
    that answer in the reference's own form; `counted` holds the checks that
    are plain counts. The reference is the configuration's plain one, over
    the generator's live rows after the window's last refresh. The control
    (`control.py`) passes through here with its own answers."""
    ref = importlib.import_module(config["reference"]["module"])
    want = ref.VIEWS[config["reference"]["view"]][0](live)
    checks = {"reference_empty": {"value": 0 if want else 1, "limit": 0}}
    for name, value in counted.items():
        checks[name] = {"value": value, "limit": 0}
    for name, answer in answers.items():
        try:
            value = ref.differ(answer(), want)
        except Exception as e:  # an answer that cannot be read is a wrong answer
            print(f"{PACKAGE}: {name}: {e!r}", file=sys.stderr)
            value = -1
        checks[name] = {"value": value, "limit": 0}
    return checks, len(want)


def served_answers(served: Served, sub, config: dict) -> dict:
    """What the timed path produced, three ways: the subscriber's
    consolidated diffs, a SELECT over pgwire and one over POST /api/sql."""
    ref = importlib.import_module(config["reference"]["module"])
    parse = ref.VIEWS[config["reference"]["view"]][1]
    select = f"SELECT * FROM {config['view']}"

    def subscribed():
        rows = sub.rows()
        if any(v != 1 for v in rows.values()):
            raise ValueError("a subscribed row has multiplicity other than 1")
        return parse(rows.keys())

    return {
        "subscribe_rows_differ": subscribed,
        "pgwire_rows_differ": lambda: parse(served.sql.query(select)),
        "http_rows_differ": lambda: parse(http_sql(served.http_port, select)),
    }


def report(checks: dict) -> bool:
    """Prints each number compared beside its limit; True where all hold."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']}){'' if holds(c) else '  <-- FAILS'}", file=sys.stderr)
    return all(holds(c) for c in checks.values())


# -- one run --------------------------------------------------------------------


def applicable(metrics: list, cell: str) -> list:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def read_metrics(entries: list, run: dict) -> dict:
    out = {}
    for entry in entries:
        spec = load_json(HERE / "metrics" / f"{entry['name']}.json")
        reader = importlib.import_module(f"{PACKAGE}.readers.{spec['reader']}")
        value = reader.read(run, **spec.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def run_cell(bench: dict, cell: dict, config: dict, traffic_spec: dict, seed: int,
             seconds: float, trace: bool, rehearse: bool) -> dict | None:
    import jax

    import materialize_tpu  # noqa: F401  (x64 and the compile cache, before any compile)

    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"{PACKAGE}: found {len(devices)} x {devices[0].platform!r}, the cell needs "
              f"{cell['chips']} TPU chip(s); nothing was run", file=sys.stderr)
        return None
    device = devices[0]
    peaks = load_json(HERE / "peaks.json")
    if not rehearse and device.device_kind not in peaks:
        raise BenchFailure(f"no peaks for device kind {device.device_kind!r} in peaks.json")
    compiles = Compiles()

    # the one seam: the class the coordinator constructs for LOAD GENERATOR
    seam_mod, seam_attr = config["generator"]["seam"].split(":")
    generator_cls = resolve(config["generator"]["class"])
    seam = importlib.import_module(seam_mod)
    original = getattr(seam, seam_attr)
    setattr(seam, seam_attr, functools.partial(generator_cls, seed=seed))

    served = sub = trace_dir = None
    recorder = Recorder()
    try:
        served = Served()
        scale = config["rehearse_scale_factor"] if rehearse else config["scale_factor"]
        phases = {"imports_and_serve_s": time.perf_counter() - T0}
        for sql in config["setup_sql"]:
            t = time.perf_counter()
            served.sql.query(sql.format(scale_factor=f"{scale:g}"))
            phases[" ".join(sql.split()[:2]).lower() + "_s"] = time.perf_counter() - t
        sub = Subscriber(served.pg_port, config["view"])
        traffic = Traffic(served, sub, traffic_spec)

        for i in range(traffic_spec["warmups"]):
            rec = traffic.refresh()
            if rec["arrival"] is None:
                raise BenchFailure(f"warm-up refresh {i} failed: {rec['error'] or sub.error!r}")
        phases["warmup_refreshes_s"] = [round(r["done"] - r["created"], 3) for r in traffic.refreshes]
        traffic.refreshes.clear()
        setup = compiles.since((0, 0, 0.0))

        tracing = trace and not rehearse
        if tracing:
            recorder.wrap_all(bench_kernels(bench), "materialize_tpu")
            trace_dir = tempfile.mkdtemp(prefix=f"{PACKAGE}-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            mark("window_start")
        in_window = compiles.snapshot()
        start, end = traffic.run(seconds)
        compiled = compiles.since(in_window)
        if tracing:
            mark("window_end")
            jax.profiler.stop_trace()
            recorder.unwrap()  # bytes are counted for exactly what the trace timed
        stats = device.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))

        leaves = device_leaves(served.dataflow(config["view"]))
        checks, reference_rows = checks_of(
            config, served.generator().live(), served_answers(served, sub, config),
            {"refreshes_undelivered": sum(1 for r in traffic.refreshes if r["ts"] is not None and r["arrival"] is None),
             "state_arrays_off_device": sum(1 for a in leaves if a.devices() != {device}) if leaves else -1})

        ok_refreshes = [r for r in traffic.refreshes if r["arrival"] is not None]
        updates_by_ts = served.generator().updates_by_ts
        counts = {
            "refreshes": len(ok_refreshes),
            "updates": sum(updates_by_ts.get(r["ts"], 0) for r in ok_refreshes),
            "warmup_refreshes": traffic_spec["warmups"],
            "reference_rows": reference_rows,
            "state_bytes": sum(int(a.nbytes) for a in leaves),
            "programs_requested_in_setup": setup["programs"],
            "programs_requested_in_window": compiled["programs"],
        }
        result = {"correct": None, "attempted": len(traffic.refreshes),
                  "failed": len(traffic.refreshes) - len(ok_refreshes), "metrics": {},
                  "device": {"platform": device.platform, "kind": device.device_kind, "count": cell["chips"]}}
        if rehearse:  # a rehearsal prints counts and `correct` only: no time of a CPU run
            result["counts"] = counts
        else:
            result["device"]["memory_peak_bytes"] = memory_peak
            samples = {
                "freshness_ms": [1e3 * (r["arrival"] - r["created"]) for r in ok_refreshes],
                "deliver_ms": [1e3 * (r["arrival"] - r["done"]) for r in ok_refreshes],
                "advance_ms": [1e3 * (r["done"] - r["created"]) for r in ok_refreshes],
                "outside_render_ms": [1e3 * (r["done"] - r["created"]) - r["render_ns"] / 1e6 for r in ok_refreshes],
            }
            run = {
                "window_s": end - start,
                "counts": counts,
                "samples": samples,
                "values": {
                    "setup_s": start - T0,
                    "setup_compile_s": setup["seconds"],
                    "setup_programs_compiled": setup["compiled"],
                    "compiles_in_window": compiled["programs"],
                    "compile_in_window_s": compiled["seconds"],
                    "render_ms": sum(r["render_ns"] for r in traffic.refreshes) / 1e6,
                    "render_steps": sum(r["render_steps"] for r in traffic.refreshes),
                    "memory_peak_bytes": memory_peak,
                    "traced_refreshes": len(traffic.refreshes) if tracing else 0,
                },
                "peaks": peaks[device.device_kind],
                "work": {"calls": recorder.calls, "bytes": recorder.bytes},
                "trace": None,
            }
            if trace:
                found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
                if len(found) != 1:
                    raise BenchFailure(f"expected one trace file, found {found}")
                run["trace"] = reduce_trace(found[0])
                result["device"]["busy_s"] = run["trace"]["busy_s"]
                result["device"]["window_s"] = run["trace"]["window_s"]
                result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                                       "idle_gaps": run["trace"]["idle_gaps"]}
            entries = applicable(bench["per_layer" if trace else "end_to_end"], cell["name"])
            result["metrics"] = read_metrics(entries, run)
            print(f"{PACKAGE}: set-up {json.dumps(phases)} compile {json.dumps(setup)} "
                  f"window {json.dumps(compiled)} counts {json.dumps(counts)} "
                  f"freshness_ms {json.dumps([round(x, 1) for x in samples['freshness_ms']])}", file=sys.stderr)
        result["checks"] = checks  # last in the line, and the last lines on standard error
        result["correct"] = report(checks)
        return result
    finally:
        setattr(seam, seam_attr, original)
        recorder.unwrap()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if sub is not None:
            sub.close()
        if served is not None:
            served.close()


def bench_kernels(bench: dict) -> dict:
    """Every kernel any per-layer metric's file asks the work recorder for."""
    kernels: dict = {}
    for entry in bench["per_layer"]:
        kernels.update(load_json(HERE / "metrics" / f"{entry['name']}.json").get("kernels", {}))
    return kernels


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's files at rehearsal scale on the CPU; counts and `correct` only")
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = one(bench["workloads"], args.workload)
    config = load_json(ROOT / one(bench["configs"], cell["config"])["file"])
    traffic_spec = load_json(HERE / "workloads" / f"{cell['traffic']}.json")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the program keeps no compile cache there
    else:
        # The compile cache lives at one fixed path inside the checkout, with no
        # size cap, whatever the machine's environment says: the program takes
        # the directory it is given (it sets none in code where this variable is
        # set). A cache capped below what a cell's programs need (the chip
        # tool's machines cap it at 192 MiB; Q3 at SF1 writes 180 MiB on top of
        # what is there) evicts every entry before its next use, and then every
        # run is a cold run.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    result = run_cell(bench, cell, config, traffic_spec, args.seed, args.seconds, bool(args.trace), args.rehearse)
    if result is None:
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
