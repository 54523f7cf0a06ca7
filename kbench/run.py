"""The benchmark's one entry point.

    python -m kbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one process that holds the chip: it builds the HTTP
apiserver and the scheduler exactly as ``hyperkube apiserver`` and
``hyperkube scheduler --batch`` build them, starts the load generator
(``kbench/loadgen.py``) as a process of its own, waits through set-up
and warm-up, measures for ``--seconds``, checks what the window
produced against the plain reference (``kbench/reference.py``), and
prints one JSON line last on standard output.

Everything a cell needs is found by name: the cell in BENCHMARK.json,
its configuration in ``kbench/configs/<config>.json``, its traffic in
``kbench/traffic/<traffic>.json``, and each per-layer metric's reader
in ``kbench/metrics/<metric>.py`` (or, for ``<base>.<suffix>``, in
``kbench/metrics/<base>.py``).

A run that finds no TPU fails and prints no result. ``--rehearse``
alone lets one through on the CPU, at the configuration's rehearsal
sizes; the tests use it.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(Exception):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started (Linux: /proc)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# -- finding a cell by name ----------------------------------------


def load_cell(name: str, rehearse: bool, root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "kbench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        cfg.update(cfg.get("rehearsal", {}))
        traffic.update(traffic.get("rehearsal", {}))

    def here(m: dict) -> bool:
        return name in m.get("workloads", [name])

    return {
        "cell": cell,
        "config": cfg,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def reader(metric: str, root: str = ROOT):
    """The per-layer metric's reader module, by name."""
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(root, "kbench", "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(f"kbench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise BenchError(f"no reader for per-layer metric {metric!r}")


# -- the process that holds the chip ------------------------------


def device_check(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"devices: platform={d0.platform} kind={d0.device_kind!r} count={len(devices)}")
    if d0.platform != "tpu" and not rehearse:
        raise BenchError(f"JAX found {d0.platform!r}, not a TPU: this benchmark runs on the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return d0, len(devices)


def build_native() -> None:
    """``make`` rebuilds the native library only when it is stale."""
    subprocess.run(
        ["make", "-C", os.path.join(ROOT, "native"), "lib"],
        check=True, stdout=subprocess.DEVNULL,
    )
    from kubernetes_tpu import native

    if not native.available():
        raise BenchError("native library built but does not load")


def _phases() -> Dict[str, tuple]:
    from kubernetes_tpu.utils.tracing import PHASE_SECONDS

    return {k[0]: v[:2] for k, v in PHASE_SECONDS.snapshot().items()}


def _scheduled() -> float:
    from kubernetes_tpu.utils import metrics

    fam = metrics.DEFAULT.get("scheduler_pods_scheduled_total")
    return fam.snapshot().get(("scheduled",), 0.0) if fam else 0.0


def _compiles() -> Dict[str, int]:
    from kubernetes_tpu.ops import ledger

    return {r["kernel"]: r["compiles"] for r in ledger.DEFAULT.rows()}


class Reading:
    """The program's counters at one instant."""

    def __init__(self):
        self.t = time.monotonic()
        self.phases = _phases()
        self.scheduled = _scheduled()
        self.compiles = _compiles()

    def delta(self, before: "Reading") -> dict:
        return {
            "seconds": self.t - before.t,
            "phases": {
                ph: (n - before.phases.get(ph, (0, 0.0))[0],
                     s - before.phases.get(ph, (0, 0.0))[1])
                for ph, (n, s) in self.phases.items()
            },
            "scheduled": self.scheduled - before.scheduled,
            "compiles": {
                k: n - before.compiles.get(k, 0)
                for k, n in self.compiles.items()
                if n > before.compiles.get(k, 0)
            },
        }


def serve(spec: dict, seed: int, seconds: float, trace: bool, trace_dir: str) -> dict:
    """Set up, warm up, measure; returns the loadgen's record with the
    program's readings over the window."""
    from kubernetes_tpu.cmd.daemons import (
        apiserver_parser, scheduler_parser, start_apiserver, start_scheduler,
    )

    cfg, traffic = spec["config"], spec["traffic"]
    srv = start_apiserver(apiserver_parser().parse_args(cfg["apiserver_args"]))
    sched = gen_proc = None
    out: dict = {}
    try:
        gen_proc = subprocess.Popen(
            [sys.executable, "-m", "kbench.loadgen"], cwd=ROOT,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        gen_proc.stdin.write(json.dumps({
            "address": srv.address, "config": cfg, "traffic": traffic,
            "seed": seed, "seconds": seconds,
        }) + "\n")
        gen_proc.stdin.flush()

        def event() -> dict:
            line = gen_proc.stdout.readline()
            if not line:
                raise BenchError(f"load generator exited ({gen_proc.wait()})")
            return json.loads(line)

        if event()["event"] != "ready":
            raise BenchError("load generator did not set up the cluster")
        log(f"cluster written at {process_age_s():.3f}s")
        sched = start_scheduler(scheduler_parser().parse_args(
            ["--server", srv.address] + cfg["scheduler_args"]
        ))
        deadline = time.monotonic() + 900
        while sched._session is None:
            if time.monotonic() > deadline:
                raise BenchError("the scheduler's session was never built")
            time.sleep(0.05)
        width = cfg.get("prewarm_scatter_width")
        if width:
            # The dirty-row scatter widths the traffic reaches beyond
            # the daemon's own pre-warm (512), through the session's
            # own prewarm: set-up, not the window, compiles them.
            sched._session.prewarm(max_scatter_width=width)
        log(f"scheduler session built at {process_age_s():.3f}s")
        gen_proc.stdin.write("go\n")
        gen_proc.stdin.flush()
        ev = event()
        if ev["event"] != "open":
            raise BenchError(f"expected the window to open, got {ev['event']}")
        out["setup_s"] = process_age_s()
        if trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = Reading()
        ev = event()
        if ev["event"] != "close":
            raise BenchError(f"expected the window to close, got {ev['event']}")
        after = Reading()
        if trace:
            import jax

            jax.profiler.stop_trace()
        out["window"] = after.delta(before)
        ev = event()
        if ev["event"] != "done":
            raise BenchError(f"expected the run to end, got {ev['event']}")
        out["run"] = ev
        out["fallbacks"] = sched.fallback_count
        out["memory_peak_bytes"] = _memory_peak()
    finally:
        if gen_proc is not None:
            if gen_proc.poll() is None:
                gen_proc.kill()
            gen_proc.wait()
        if sched is not None:
            sched.stop()
        srv.stop()
    return out


def _memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


# -- numbers ------------------------------------------------------


def pct(values: List[float], q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    k = math.ceil(round(q * len(s), 9)) - 1
    return s[max(0, min(len(s) - 1, k))]


def end_to_end(spec: dict, out: dict) -> tuple:
    run = out["run"]
    traffic = spec["traffic"]
    window = run["window_pods"]
    bound = {e[2]: e[4] for e in run["watch"] if e[1] == "B"}
    due = run["due"]
    refused = sum(1 for r in run["creates"] if not 200 <= r[4] < 300)
    lats = [
        (bound[n] - due[n]) * 1e3 if n in bound else float("inf")
        for n in window
    ]
    attempted = len(window)
    failed = sum(1 for x in lats if x == float("inf"))
    values: Dict[str, float] = {}
    if lats:
        values["bind_p50_ms"] = pct(lats, 0.50)
        values["bind_p99_ms"] = pct(lats, 0.99)
    if traffic["loop"] == "closed":
        span = run["rate_end"] - run["open"]
        if span > 0:
            values["bound_pods_per_s"] = sum(1 for n in window if n in bound) / span
    else:
        values["bound_pods_per_s"] = sum(
            1 for t in bound.values() if run["open"] <= t < run["close"]
        ) / (run["close"] - run["open"])
        calls = [
            (r[3] - r[2]) * 1e3 for r in run["creates"] + run["deletes"]
            if run["open"] <= r[1] < run["close"]
        ]
        if calls:
            values["api_call_p99_ms"] = pct(calls, 0.99)
    values["setup_s"] = out["setup_s"]
    late = [
        max(0.0, r[2] - r[1]) for r in run["creates"] + run["deletes"]
        if run["open"] <= r[1] < run["close"]
    ]
    notes = [
        f"window: {attempted} pods due, {failed} never bound, "
        f"{refused} create requests refused, {len(run['errors'])} transport errors",
        f"generator lateness in the window: max {max(late, default=0) * 1e3:.3f} ms, "
        f"p99 {pct(late, 0.99) * 1e3 if late else 0:.3f} ms over {len(late)} requests",
    ]
    if lats:
        # Readings, not metrics: their run-to-run spread is wider than
        # any bound BENCHMARK.json may set (PERF.md §2).
        notes.append(
            f"create-to-bind: p50 {values['bind_p50_ms']:.3f} ms, "
            f"p99 {values['bind_p99_ms']:.3f} ms over {len(lats)} pods"
        )
    return values, attempted, failed, notes


def per_layer(spec: dict, out: dict, trace_dir: str, device_kind: str) -> tuple:
    from kbench import gen, trace as tracemod

    reduced = None
    path = tracemod.find_xplane(trace_dir)
    if path is not None:
        reduced = tracemod.reduce(tracemod.load(path))
    w = out["window"]
    ctx = {
        "phases": w["phases"], "scheduled": w["scheduled"],
        "window_s": w["seconds"], "trace": reduced,
        "device_kind": device_kind, "config": spec["config"],
        "traffic": spec["traffic"], "services": gen.services(spec["config"]),
        "notes": [],
    }
    values = {}
    for m in spec["per_layer"]:
        v = reader(m["name"]).read(ctx)
        if v is not None:
            values[m["name"]] = v
    return values, reduced, ctx["notes"]


def prepare(chips: int, rehearse: bool):
    """Compile cache, device check, native library: the part of set-up
    that comes before a cell's cluster. Returns (device, count)."""
    sys.path.insert(0, ROOT)
    from kubernetes_tpu.utils.compile_cache import place_compile_cache

    cache = place_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    device, count = device_check(chips, rehearse)
    build_native()
    return device, count


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, device, count) -> dict:
    """One run of a prepared cell: the result line's object, with the
    numbers compared under its last key, ``checks``."""
    from kbench import reference

    with tempfile.TemporaryDirectory(prefix="kbench-trace-") as trace_dir:
        out = serve(spec, seed, seconds, trace, trace_dir)
        checks = reference.check(spec["config"], spec["traffic"], seed, out["run"])
        checks["fallbacks"] = {"value": out["fallbacks"], "limit": 0}
        e2e, attempted, failed, notes = end_to_end(spec, out)
        result_device = {
            "platform": device.platform, "kind": device.device_kind,
            "count": count, "memory_peak_bytes": out["memory_peak_bytes"],
        }
        breakdown = None
        if trace:
            metrics, reduced, more = per_layer(spec, out, trace_dir, device.device_kind)
            notes += more
            if reduced is not None:
                result_device["busy_s"] = reduced["busy_s"]
                result_device["window_s"] = out["window"]["seconds"]
                breakdown = {
                    "device_ops": [list(x) for x in reduced["device_ops"]],
                    "idle_gaps": reduced["idle_gaps"],
                }
            names = {m["name"]: m["unit"] for m in spec["per_layer"]}
        else:
            # A metric named <quantity>.<suffix> reports <quantity>:
            # cells whose spreads need different bounds split its name.
            metrics = {
                m["name"]: e2e[m["name"].split(".")[0]]
                for m in spec["end_to_end"] if m["name"].split(".")[0] in e2e
            }
            names = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    w = out["window"]
    log(f"compilations inside the window: {sum(w['compiles'].values())} {w['compiles']}")
    log("scheduler phases over the window (spans, seconds): " + ", ".join(
        f"{ph} {n} {sec:.6f}" for ph, (n, sec) in sorted(w["phases"].items()) if n
    ))
    for n in notes:
        log(n)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": {
            k: {"value": v, "unit": names[k]} for k, v in metrics.items() if k in names
        },
        "device": result_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["readings"] = e2e  # for kbench.control; report() leaves it out
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """The compared numbers, each beside its limit, as the last lines
    of standard error; then the result line, last on standard output."""
    result.pop("readings", None)
    result["checks"] = {
        k: {"value": c["value"], "limit": c["limit"]}
        for k, c in result["checks"].items()
    }
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="rehearsal sizes; a CPU is allowed (tests only)")
    args = p.parse_args(argv)
    try:
        spec = load_cell(args.workload, args.rehearse)
        device, count = prepare(spec["cell"]["chips"], args.rehearse)
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace), device, count)
    except (BenchError, subprocess.CalledProcessError, ImportError, OSError) as e:
        print(f"kbench: FAILED: {e!r}", file=sys.stderr)
        return 1
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
