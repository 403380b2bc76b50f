"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Four things come out, all inside the harness's ``bench.window``
annotation on the host:

  * busy and idle: the union of the device's op intervals, over the
    window's length (averaged over the devices traced);
  * each kernel's device time and call count, matched (`KERNELS`) in
    the op's name and the text of its stats (HLO op, its long name,
    source), so a kernel is found whatever name the compiler gives it;
  * the device ops that took most time;
  * the idle gaps, each named by the innermost ``bench.*`` annotation
    the host was in at the gap's middle, and within it by the innermost
    other host event there, when there is one.

Run as a script on a trace directory to print the reduction.
"""
from __future__ import annotations

import glob
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# kernel -> alternatives, each a tuple of substrings that must all appear
# in a device op's name or stats.  On a v5e the step kernel's op is the
# Mosaic custom call named after its jitted wrapper:
#   %run_packed.1 = (...) custom-call(...), custom_call_target="tpu_custom_call"
# The custom-call target is required as well, so that no other op of the
# wrapper's program (the layout copies around the kernel) counts, whatever
# stats a later JAX attaches to them.
KERNELS = {"comefa_step": (("tpu_custom_call", "run_packed"),
                           ("tpu_custom_call", "_step_kernel"))}
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def find_trace(directory) -> Optional[Path]:
    files = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return Path(files[-1]) if files else None


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _device_events(line) -> List[Tuple[str, int, int, str]]:
    """(name, start, end, text to match kernels in) of each device op."""
    out = []
    for e in line.events:
        text = " ".join([e.name] + [v for _, v in e.stats
                                    if isinstance(v, str)])
        out.append((e.name, int(e.start_ns),
                    int(e.start_ns + e.duration_ns), text))
    return out


def load(path) -> Dict:
    """Planes of one trace as plain lists: {"device": {plane: [(name,
    start, end, text)]}, "host": [(name, start, end, thread)]}."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                out["device"][plane.name] = _device_events(lines[OPS_LINE])
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out["host"] += [ev + (i,) for ev in _events(line)]
    return out


def _union(intervals) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _label_gaps(host, gaps) -> Dict[str, float]:
    """Seconds of idle per label; each gap is named at its middle by the
    innermost ``bench.*`` annotation, then the innermost other host event.

    One sweep: gaps and host events both in time order, keeping only the
    events that still cover the current gap's middle."""
    host = sorted(host, key=lambda h: h[1])
    out, active, i = defaultdict(float), [], 0
    for g0, g1 in sorted(gaps):
        mid = (g0 + g1) // 2
        while i < len(host) and host[i][1] <= mid:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[2] >= mid]
        names = {}
        for name, s, e, _ in active:
            kind = name.startswith("bench.")
            if kind not in names or e - s < names[kind][1]:
                names[kind] = (name, e - s)
        outer = names.get(True, ("outside",))[0]
        inner = names.get(False, (None,))[0]
        label = outer if inner is None else f"{outer} / {inner}"
        out[label] += (g1 - g0) * 1e-9
    return out


def reduce(trace: Dict, top: int = 10) -> Optional[Dict]:
    """The reduction of `load`'s output, or None without a device plane
    or a window annotation."""
    wins = [(s, e) for name, s, e, _ in trace["host"] if name == WINDOW]
    if not wins or not trace["device"]:
        return None
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    window_s = (w1 - w0) * 1e-9
    busy, ops = [], defaultdict(lambda: [0, 0.0])
    kernels = {k: {"calls": 0, "seconds": 0.0} for k in KERNELS}
    gaps_by = defaultdict(float)
    host = [h for h in trace["host"] if h[2] >= w0 and h[1] <= w1]
    for plane, events in trace["device"].items():
        inside = [(n, max(s, w0), min(e, w1), text)
                  for n, s, e, text in events if e > w0 and s < w1]
        merged = _union((s, e) for _, s, e, _ in inside)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for n, s, e, text in inside:
            ops[n][0] += 1
            ops[n][1] += (e - s) * 1e-9
            for k, alts in KERNELS.items():
                if any(all(p in text for p in alt) for alt in alts):
                    kernels[k]["calls"] += 1
                    kernels[k]["seconds"] += (e - s) * 1e-9
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, sec in _label_gaps(host, gaps).items():
            gaps_by[label] += sec
    n_dev = len(trace["device"])
    for k in kernels:
        kernels[k]["seconds"] /= n_dev
    busy_s = sum(busy) / n_dev
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])[:top]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": n_dev,
        "kernels": kernels,
        "device_ops": [[n, v[1] / n_dev] for n, v in top_ops],
        "idle_gaps": [[n, s / n_dev] for n, s in top_gaps],
    }


def reduce_dir(directory) -> Optional[Dict]:
    path = find_trace(directory)
    return None if path is None else reduce(load(path))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1]), indent=1))
