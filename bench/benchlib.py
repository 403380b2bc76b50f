"""Shared pieces of the harness: files by name, seeds, compiles, spans.

Nothing here imports the program; `run.py` and the system modules under
``systems/`` do.
"""
from __future__ import annotations

import bisect
import importlib.util
import json
import os
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class WindowClosed(Exception):
    """Raised inside the program's call stack to end the measured window."""


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import a Python file by path (bench file names hold '-' and '.')."""
    path = Path(path)
    mod_name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(benchmark: Dict, workload: str) -> Dict:
    for w in benchmark["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")


def config_entry(benchmark: Dict, name: str) -> Dict:
    for c in benchmark["configs"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"bench: no configuration {name!r} in BENCHMARK.json")


def applies(metric: Dict, workload: str, end_to_end: Dict[str, Dict]) -> bool:
    """Whether a metric is reported in this cell (its `workloads` key, or
    every cell that reports the end-to-end metric it moves)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    return applies(end_to_end[moves], workload, end_to_end)


def jax_key(jax, seed: int):
    """A JAX PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2)
    key = jax.random.PRNGKey(int(words[0]))
    return jax.random.fold_in(key, int(words[1] >> 1))


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``.jax_cache/`` at the checkout root (a fixed path, so it hits)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileWatch:
    """Counts executables JAX builds or loads (compile or cache hit)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# obs spans: totals and self times over a window
# ---------------------------------------------------------------------------

class Spans:
    """Wall-clock obs spans of one window, indexed for nesting queries."""

    def __init__(self, events: Iterable, t0_us: float, t1_us: float):
        evs = [e for e in events if e.track == "wall"
               and e.ts >= t0_us and e.ts + e.dur <= t1_us]
        evs.sort(key=lambda e: e.ts)
        self.events = evs
        self._starts = [e.ts for e in evs]
        self.window_us = t1_us - t0_us

    def named(self, name: str) -> List:
        return [e for e in self.events if e.name == name]

    def total_us(self, name: str) -> float:
        return float(sum(e.dur for e in self.named(name)))

    def children(self, parent, pred: Callable) -> List:
        end = parent.ts + parent.dur
        lo = bisect.bisect_left(self._starts, parent.ts)
        hi = bisect.bisect_right(self._starts, end)
        return [e for e in self.events[lo:hi] if e is not parent
                and e.tid == parent.tid and e.ts + e.dur <= end
                and pred(e)]

    def self_us(self, name: str, child: Callable) -> float:
        """Sum over `name` spans of their time not covered by children."""
        total = 0.0
        for p in self.named(name):
            total += p.dur - union_length(
                (c.ts, c.ts + c.dur) for c in self.children(p, child))
        return total


def union_length(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def counter_delta(before: Dict, after: Dict) -> Dict[str, float]:
    """Flat ``name{labels}`` counter increases between two snapshots."""
    out = {}
    for k, v in after.items():
        if isinstance(v, (int, float)):
            d = v - before.get(k, 0)
            if d:
                out[k] = d
    return out


class RunView:
    """What a per-layer metric reader may read from one traced run."""

    def __init__(self, *, spans: Optional[Spans], counters: Dict[str, float],
                 device: Optional[Dict], peaks: Dict, facts: Dict):
        self.spans = spans
        self.counters = counters
        self.device = device
        self.peaks = peaks
        self.facts = facts

    def counter(self, prefix: str, **labels) -> float:
        """Sum of the window's counter series named `prefix` whose labels
        include every given ``key=value``."""
        want = [f"{k}={v}" for k, v in labels.items()]
        total = 0.0
        for key, val in self.counters.items():
            name, _, tag = key.partition("{")
            if name != prefix:
                continue
            tags = tag.rstrip("}").split(",") if tag else []
            if all(w in tags for w in want):
                total += val
        return total
