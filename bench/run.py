#!/usr/bin/env python3
"""Benchmark harness: run one cell of BENCHMARK.json and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by the names in ``BENCHMARK.json``: the cell's
configuration file (``configs/<config>.json``, with its plain reference
``configs/<config>_ref.py`` beside it), its traffic mix
(``traffic/<traffic>.json``), the module that runs the configuration's
system (``systems/<system>.py``) and one reader per per-layer metric
(``metrics/<metric>.py``).  A new cell is new files and new entries.

A run: set-up (device check, weights or operands from ``--seed``,
warm-up of every shape the window uses), then a window of ``--seconds``
on the host clock, then - with the program's state freed - the
comparison with the plain reference that decides ``correct``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window also records the program's obs spans and a
profiler trace, and the result carries the per-layer metrics, the
device's busy time and a breakdown.  The last line of standard output
is the result; the last lines of standard error are the compared
numbers beside their limits.

Off a TPU it exits non-zero and prints no result.  ``--rehearse`` runs
the same path on the CPU at the configuration's tiny ``rehearsal``
sizes, with the Pallas kernel in interpret mode; ``--control`` puts the
lower-precision control in the program's place in the comparison, on
the same work, so its ``correct`` has to come out false; ``--fault``
plants one of `faults.FAULTS` under the timed window, after set-up.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import benchlib  # noqa: E402
import faults  # noqa: E402
import trace_reduce  # noqa: E402

SPAN_CAPACITY = 4_000_000


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, interpret-mode kernel")
    ap.add_argument("--control", action="store_true",
                    help="judge the lower-precision control in the "
                    "program's place")
    ap.add_argument("--fault", choices=faults.FAULTS,
                    help="plant a fault under the timed window")
    return ap.parse_args(argv)


def _plain(o):
    """JSON for numpy scalars."""
    return o.item()


def _verdict(checks: dict, invalid: list) -> bool:
    return not invalid and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    bench = benchlib.load_json(ROOT / "BENCHMARK.json")
    wl = benchlib.cell(bench, args.workload)
    entry = benchlib.config_entry(bench, wl["config"])
    cfg_path = ROOT / entry["file"]
    config = benchlib.load_json(cfg_path)
    mix = benchlib.load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < wl["chips"]):
        print(f"bench: {wl['name']} needs {wl['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 1
    benchlib.enable_compile_cache(jax)
    watch = benchlib.CompileWatch(jax)
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    runner = benchlib.load_module(BENCH / "systems" /
                                  f"{config['system']}.py")
    reference = benchlib.load_module(
        cfg_path.with_name(f"{wl['config']}_ref.py"))
    system = runner.System(config=config, mix=mix, seed=args.seed,
                           rehearse=args.rehearse, reference=reference)
    tracing = args.trace == 1
    system.setup(annotate=tracing)
    if args.fault:
        faults.install(args.fault, config["system"])

    tmp = None
    if tracing:
        obs_trace.configure(enabled=True, capacity=SPAN_CAPACITY)
        obs_trace.get_tracer().clear()
        tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    snap0 = obs_metrics.flatten(obs_metrics.snapshot())
    compiles0 = watch.count
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    with (jax.profiler.TraceAnnotation(trace_reduce.WINDOW) if tracing
          else contextlib.nullcontext()):
        result = system.window(t0, args.seconds)
    compiles = watch.count - compiles0
    snap1 = result.get("window_end_snap") or obs_metrics.flatten(
        obs_metrics.snapshot())
    events = []
    if tracing:
        jax.profiler.stop_trace()
        events = obs_trace.get_tracer().events()
        obs_trace.configure(enabled=False)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    counters = benchlib.counter_delta(snap0, snap1)
    invalid = system.validity(counters)
    if compiles:
        invalid.append(f"{compiles} executable(s) compiled or loaded "
                       "inside the window")
    if tracing and len(events) >= SPAN_CAPACITY:
        invalid.append("the span buffer dropped spans")

    system.free()
    verdict = system.check(control=args.control)
    checks = verdict["checks"]
    correct = _verdict(checks, invalid)

    metrics, out_extra = {}, {}
    if tracing:
        reduced = trace_reduce.reduce_dir(tmp.name)
        tmp.cleanup()
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out_extra["breakdown"] = {"device_ops": reduced["device_ops"],
                                      "idle_gaps": reduced["idle_gaps"]}
        peaks = benchlib.load_json(BENCH / "peaks.json")
        if dev.device_kind not in peaks and not args.rehearse:
            raise SystemExit(f"bench: no peaks for {dev.device_kind!r}")
        t_lo, t_hi = system.span_window(events)
        view = benchlib.RunView(
            spans=benchlib.Spans(events, t_lo, t_hi), counters=counters,
            device=reduced, peaks=peaks.get(dev.device_kind, {}),
            facts=system.facts())
        for m in bench["per_layer"]:
            if not benchlib.applies(m, wl["name"], e2e):
                continue
            reader = benchlib.load_module(BENCH / "metrics" /
                                          f"{m['name']}.py")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(system.end_to_end(), setup_s=setup_s)
        for name, m in e2e.items():
            if benchlib.applies(m, wl["name"], e2e):
                metrics[name] = {"value": values[name], "unit": m["unit"]}

    attempted = system.attempted()
    # compared answers beyond their limits, counted against the work
    failed = min(attempted, sum(c.get("bad", 0) for c in checks.values()))
    line = {"correct": correct, "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device,
            **out_extra,
            "info": dict(verdict["info"], invalid=invalid,
                         compiles_in_window=compiles, window_s=result[
                             "window_s"], setup_s=setup_s),
            "checks": {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in checks.items()}}
    for reason in invalid:
        print(f"invalid: {reason}", file=sys.stderr)
    for k, c in checks.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, default=_plain), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
