"""The unified telemetry layer: metrics registry, tracer, exporters.

Covers the three `repro.obs` modules plus their integration with the
CoMeFa stack:

  * registry semantics - labelled counters/gauges/histograms, snapshot /
    reset lifecycle, flatten, kind-mismatch errors, thread safety;
  * the `block.ENCODE_CACHE_STATS` compatibility shim and the
    two-independent-sessions regression the registry reset fixes;
  * array-vs-grid parity of the registry-backed ``host_syncs`` /
    ``device_puts`` counters against the legacy instance attributes;
  * tracer behaviour - nesting under exceptions, disabled mode emitting
    nothing (and costing one shared NULL_SPAN), the bounded ring buffer,
    model-time spans from `Schedule.emit_trace`;
  * Chrome trace export round-tripping through ``json.loads`` with valid
    ``ph``/``ts``/``dur`` fields on both the wall-clock and
    modeled-cycles processes;
  * the ``REPRO_COMEFA_TRACE`` smoke path: a traced per-slot GEMV sweep
    must produce a non-empty trace with both time domains present;
  * the profiler clock: enabled spans appear as host events of a
    `jax.profiler` trace (closing on exceptions too), disabled spans build
    no annotation and import no JAX;
  * where the grid's host buckets nest (upload, program upload, stack,
    wait, place, extract) in both batched-GEMV modes, and the exact
    ``comefa.transfer_bytes`` of a small known array and grid.
"""
import glob
import json
import os
import re
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core.comefa import (ComefaArray, ComefaGrid, block, layout,
                               program, schedule)
from repro.obs import export, metrics, trace

BITS = 4


def _mul_prog():
    n = BITS
    return program.mul(list(range(n)), list(range(n, 2 * n)),
                       list(range(2 * n, 4 * n)))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_snapshot():
    reg = metrics.Registry()
    c = reg.counter("requests")
    c.inc(kind="a")
    c.inc(2, kind="b")
    c.inc()
    assert c.value(kind="a") == 1
    assert c.value(kind="b") == 2
    assert c.value() == 1
    assert c.value(kind="missing") == 0
    snap = reg.snapshot()
    assert snap["requests"]["kind"] == "counter"
    assert {"labels": {"kind": "b"}, "value": 2} \
        in snap["requests"]["series"]
    flat = metrics.flatten(snap)
    assert flat["requests{kind=b}"] == 2
    assert flat["requests"] == 1


def test_label_order_is_canonical():
    reg = metrics.Registry()
    c = reg.counter("c")
    c.inc(a="1", b="2")
    c.inc(b="2", a="1")
    assert c.value(a="1", b="2") == 2
    assert len(c.series()) == 1


def test_reset_keeps_handles_valid():
    reg = metrics.Registry()
    c = reg.counter("c")
    c.inc(k="v")
    reg.reset()
    assert c.value(k="v") == 0
    assert reg.snapshot() == {}        # empty series are omitted
    c.inc(k="v")                       # the pre-reset handle still works
    assert reg.counter("c").value(k="v") == 1


def test_kind_mismatch_raises():
    reg = metrics.Registry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_gauge_and_histogram():
    reg = metrics.Registry()
    g = reg.gauge("g")
    g.set(5, slot="0")
    g.add(2, slot="0")
    assert g.value(slot="0") == 7
    h = reg.histogram("h")
    for v in (1, 5, 3):
        h.observe(v)
    assert h.value() == {"count": 3, "sum": 9, "min": 1, "max": 5}
    assert h.value(absent="x") == {"count": 0, "sum": 0, "min": 0,
                                   "max": 0}
    snap = reg.snapshot()
    assert snap["h"]["series"][0]["value"]["count"] == 3


def test_counter_thread_safety():
    reg = metrics.Registry()
    c = reg.counter("c")

    def worker():
        for _ in range(1000):
            c.inc(kind="t")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(kind="t") == 8000


# ---------------------------------------------------------------------------
# ENCODE_CACHE_STATS compatibility shim + the global-state regression
# ---------------------------------------------------------------------------

def test_encode_cache_stats_mapping_protocol():
    stats = block.ENCODE_CACHE_STATS
    stats.update(hits=0, misses=0, device_hits=0, device_misses=0)
    assert stats == {"hits": 0, "misses": 0, "device_hits": 0,
                     "device_misses": 0}
    stats["hits"] = 3
    assert stats["hits"] == 3
    assert {**stats}["hits"] == 3
    assert len(stats) == 4 and set(stats) == set(stats._KEYS)
    with pytest.raises(KeyError):
        stats["nope"]
    with pytest.raises(KeyError):
        stats["nope"] = 1
    with pytest.raises(TypeError):
        del stats["hits"]
    # the shim is a live view over the registry counter, not a copy
    metrics.counter("comefa.encode_cache").inc(event="hits")
    assert stats["hits"] == 4


def test_two_independent_sessions_see_identical_stats():
    """The regression the registry fixes: session 2 must not inherit
    session 1's counts (module-level dict leakage across tests)."""
    def session():
        metrics.reset()
        block._ENCODE_CACHE.clear()
        arr = ComefaArray(n_blocks=1)
        a = np.arange(160).reshape(1, 160) % (1 << BITS)
        layout.place(arr, a, 0, BITS)
        layout.place(arr, a, BITS, BITS)
        arr.run(_mul_prog())
        arr.run(_mul_prog())           # structurally equal rebuild: hit
        layout.extract(arr, 2 * BITS, 2 * BITS)
        return dict(block.ENCODE_CACHE_STATS), arr.host_syncs

    first, syncs1 = session()
    second, syncs2 = session()
    assert first == second
    assert first["misses"] == 1 and first["hits"] == 1
    assert syncs1 == syncs2


# ---------------------------------------------------------------------------
# array/grid counter parity
# ---------------------------------------------------------------------------

def test_host_sync_device_put_registry_parity():
    arr = ComefaArray(n_blocks=1)
    a = np.arange(160).reshape(1, 160) % (1 << BITS)
    layout.place(arr, a, 0, BITS)
    layout.place(arr, a, BITS, BITS)
    arr.run(_mul_prog())
    layout.extract(arr, 2 * BITS, 2 * BITS)

    grid = ComefaGrid(2, n_blocks=1)
    for g in range(2):
        layout.place(grid.slot(g), a, 0, BITS)
        layout.place(grid.slot(g), a, BITS, BITS)
    grid.run(_mul_prog())
    layout.extract(grid.slot(0), 2 * BITS, 2 * BITS)

    syncs = metrics.counter("comefa.host_syncs")
    puts = metrics.counter("comefa.device_puts")
    assert syncs.value(kind="array") == arr.host_syncs > 0
    assert puts.value(kind="array") == arr.device_puts > 0
    assert syncs.value(kind="grid") == grid.host_syncs > 0
    assert puts.value(kind="grid") == grid.device_puts > 0
    # dispatches carry {kind, engine} labels whatever engine is active
    disp = metrics.counter("comefa.dispatches").series()
    kinds = {dict(k).get("kind") for k in disp}
    assert {"array", "grid"} <= kinds


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_disabled_tracer_emits_nothing():
    assert not trace.enabled()
    s = trace.span("x", a=1)
    assert s is trace.NULL_SPAN
    assert trace.span("y") is s        # one shared no-op instance
    with s as sp:
        sp.set(b=2)
    trace.model_span("m", 0, 10)
    assert len(trace.get_tracer()) == 0


def test_span_nesting_under_exception():
    trace.configure(enabled=True)
    with pytest.raises(ValueError):
        with trace.span("outer", depth=0):
            with trace.span("inner"):
                raise ValueError("boom")
    evs = trace.get_tracer().events()
    names = [e.name for e in evs]
    assert names == ["inner", "outer"]  # inner closes first: nesting holds
    assert all(e.attrs.get("error") == "ValueError" for e in evs)
    assert all(e.dur >= 0 for e in evs)


def test_span_set_attaches_attrs():
    trace.configure(enabled=True)
    with trace.span("run", program="mul") as sp:
        sp.set(cycles=42)
    ev = trace.get_tracer().events()[-1]
    assert ev.attrs == {"program": "mul", "cycles": 42}


def test_ring_buffer_bounds_memory():
    trace.configure(enabled=True, capacity=8)
    for i in range(20):
        with trace.span(f"s{i}"):
            pass
    tracer = trace.get_tracer()
    assert len(tracer) == 8 == tracer.capacity
    assert [e.name for e in tracer.events()] == \
        [f"s{i}" for i in range(12, 20)]
    trace.configure(capacity=trace.DEFAULT_CAPACITY)


def test_schedule_emit_trace_model_spans():
    trace.configure(enabled=True)
    sched = schedule.Schedule([(10, 30, 5), (10, 30, 5)], name="t")
    n = sched.emit_trace(track=3)
    assert n == 6
    evs = [e for e in trace.get_tracer().events()
           if e.track == trace.MODEL_TRACK]
    assert len(evs) == 6
    assert all(e.tid == 3 for e in evs)
    # tile 1's load overlaps tile 0's compute: the LCU pipeline shows
    by = {(e.attrs["tile"], e.attrs["phase"]): e for e in evs}
    assert by[(1, "load")].ts < by[(0, "compute")].ts \
        + by[(0, "compute")].dur
    trace.configure(enabled=False)
    assert sched.emit_trace() == 0             # disabled -> no-op


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_round_trips(tmp_path):
    trace.configure(enabled=True)
    with trace.span("encode", program="mul8"):
        pass
    trace.model_span("tile/load", 0, 100, track_id=1, tile=0)
    path = tmp_path / "trace.json"
    export.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert {e["pid"] for e in xs} == {export.WALL_PID, export.MODEL_PID}
    for e in xs:
        assert isinstance(e["ts"], float) and e["ts"] >= 0
        assert isinstance(e["dur"], float) and e["dur"] >= 0
        assert e["name"]
    wall = next(e for e in xs if e["pid"] == export.WALL_PID)
    assert wall["args"]["program"] == "mul8"
    model = next(e for e in xs if e["pid"] == export.MODEL_PID)
    assert model["ts"] == 0.0 and model["dur"] == 100.0
    assert model["tid"] == 1
    proc_names = {e["pid"]: e["args"]["name"] for e in ms
                  if e["name"] == "process_name"}
    assert proc_names[export.WALL_PID] == "wall-clock"
    assert "modeled-cycles" in proc_names[export.MODEL_PID]


def test_metrics_summary_derived_rates():
    c = metrics.counter("comefa.encode_cache")
    c.inc(3, event="hits")
    c.inc(1, event="misses")
    metrics.counter("comefa.host_syncs").inc(2, kind="array")
    summary = export.metrics_summary()
    assert summary["derived"]["encode_cache_hit_rate"] == 0.75
    assert summary["derived"]["host_syncs_total"] == 2
    assert summary["counters"]["comefa.encode_cache{event=hits}"] == 3


def test_metrics_summary_recode_and_cache_derived():
    """spec/plan cache hit rates + the recode selection histogram round-
    trip through the summary (and are absent when never bumped)."""
    empty = export.metrics_summary()
    for key in ("spec_cache_hit_rate", "plan_cache_hit_rate",
                "recode_selection"):
        assert key not in empty["derived"]
    sc = metrics.counter("comefa.spec_cache")
    sc.inc(6, event="hits")
    sc.inc(2, event="misses")
    pc = metrics.counter("comefa.plan_cache")
    pc.inc(1, event="hits")
    pc.inc(3, event="misses")
    sel = metrics.counter("comefa.recode_selected")
    sel.inc(5, choice="naive")
    sel.inc(2, choice="naf")
    sel.inc(4, choice="broadcast")
    summary = export.metrics_summary()
    assert summary["derived"]["spec_cache_hit_rate"] == 0.75
    assert summary["derived"]["plan_cache_hit_rate"] == 0.25
    assert summary["derived"]["recode_selection"] == {
        "naive": 5, "naf": 2, "broadcast": 4}
    assert summary["counters"]["comefa.recode_selected{choice=naf}"] == 2
    # the summary block must stay JSON-serializable for the nightly file
    json.loads(json.dumps(summary["derived"]))


def test_metrics_summary_selection_visible_after_auto_gemv():
    """An actual recode="auto" dispatch leaves its decisions readable in
    the summary - the 'counters visible' half of the acceptance bar."""
    from repro.kernels import comefa_sim

    rng = np.random.default_rng(3)
    g, k, n, wb, xb = 2, 6, 8, 3, 4
    w = rng.integers(0, 1 << wb, size=(g, k, n))
    x = rng.integers(0, 1 << xb, size=(g, k))
    comefa_sim.comefa_gemv_batched(w, x, w_bits=wb, x_bits=xb,
                                   acc_bits=14, recode="auto")
    summary = export.metrics_summary()
    hist = summary["derived"]["recode_selection"]
    assert sum(hist.values()) > 0
    assert set(hist) <= {"naive", "booth", "naf", "broadcast"}


# ---------------------------------------------------------------------------
# the REPRO_COMEFA_TRACE end-to-end smoke (tier-1)
# ---------------------------------------------------------------------------

def test_env_var_traced_sweep_produces_valid_trace(tmp_path, monkeypatch):
    """`REPRO_COMEFA_TRACE=...` + a run_per_slot GEMV sweep must yield a
    non-empty Chrome trace carrying BOTH time domains: wall-clock spans
    (encode / dispatch / host sync) and the per-tile load/compute/unload
    model-cycle spans of every slot's schedule."""
    from repro.kernels import comefa_sim

    path = tmp_path / "comefa-trace.json"
    monkeypatch.setenv(trace.ENV_VAR, str(path))
    assert trace.configure_from_env()
    trace.get_tracer().clear()

    rng = np.random.default_rng(7)
    g, k, n, wb, xb = 2, 4, 8, 3, 4
    w = rng.integers(0, 1 << wb, size=(g, k, n))
    x = rng.integers(0, 1 << xb, size=(g, k))
    y = comefa_sim.comefa_gemv_batched(w, x, w_bits=wb, x_bits=xb,
                                       acc_bits=16, recode="naive")
    assert np.array_equal(y, np.einsum("gkn,gk->gn", w, x))

    assert trace.flush() == str(path)
    doc = json.loads(path.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs, "traced sweep produced an empty trace"
    wall = {e["name"] for e in xs if e["pid"] == export.WALL_PID}
    assert "comefa.encode" in wall
    assert "grid.run_per_slot" in wall
    assert "grid.host_sync" in wall
    model = [e for e in xs if e["pid"] == export.MODEL_PID]
    assert {e["args"]["phase"] for e in model} == \
        {"load", "compute", "unload"}
    assert {e["tid"] for e in model} == set(range(g))  # one track/slot


# ---------------------------------------------------------------------------
# serving span coverage: the prime loop attributes every token position
# ---------------------------------------------------------------------------

def test_generate_prime_emits_per_token_spans():
    """The prompt-replay loop must emit one bounded child span per token
    position (only when tracing is on - disabled runs share NULL_SPAN),
    so a trace attributes host-sync time to individual prime steps."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import common, lm
    from repro.serve import engine

    cfg = common.reduced(configs.get("smollm-360m"), vocab=32, n_layers=1,
                         d_model=32, d_ff=64, n_heads=2, kv_heads=2,
                         head_dim=16, dtype="float32")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    # disabled: the loop allocates nothing (shared no-op span)
    assert not trace.enabled()
    engine.generate(params, prompt, cfg, steps=1, max_len=8)
    assert len(trace.get_tracer()) == 0

    trace.configure(enabled=True)
    engine.generate(params, prompt, cfg, steps=2, max_len=8)
    names = [e.name for e in trace.get_tracer().events()]
    assert names.count("serve.prime_token") == prompt.shape[1]
    assert names.count("serve.prime") == 1
    assert names.count("serve.decode_step") == 2
    steps = [e.attrs["step"] for e in trace.get_tracer().events()
             if e.name == "serve.prime_token"]
    assert steps == list(range(prompt.shape[1]))
    # children close before the parent: every prime_token precedes prime
    assert max(i for i, n in enumerate(names)
               if n == "serve.prime_token") < names.index("serve.prime")


# ---------------------------------------------------------------------------
# the profiler clock: obs spans as jax.profiler annotations
# ---------------------------------------------------------------------------

def _host_events(directory):
    """(name, start_ns, end_ns) of every host event of a profiler trace."""
    import jax
    path, = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_enabled_spans_reach_the_profiler_trace(tmp_path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    trace.configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span("obs.outer", a=1):
            with trace.span("obs.inner"):
                pass
            with pytest.raises(ValueError):
                with trace.span("obs.raised"):
                    raise ValueError("boom")
    finally:
        jax.profiler.stop_trace()
    events = {name: (s, e) for name, s, e in _host_events(tmp_path)
              if name.startswith("obs.")}
    assert set(events) == {"obs.outer", "obs.inner", "obs.raised"}
    (o0, o1) = events["obs.outer"]
    for child in ("obs.inner", "obs.raised"):
        c0, c1 = events[child]
        assert o0 <= c0 <= c1 <= o1
    assert [e.name for e in trace.get_tracer().events()] == \
        ["obs.inner", "obs.raised", "obs.outer"]


class _FakeAnnotation:
    made = []

    def __init__(self, name):
        self.name = name
        self.open = False
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False
        return False


def test_disabled_span_builds_no_annotation(monkeypatch):
    monkeypatch.setattr(trace, "_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "made", [])
    assert trace.span("off") is trace.NULL_SPAN
    with trace.span("off"):
        pass
    assert _FakeAnnotation.made == []

    trace.configure(enabled=True)
    with pytest.raises(KeyError):
        with trace.span("on", x=1):
            assert [a.open for a in _FakeAnnotation.made] == [True]
            raise KeyError("closed on unwinding")
    ann, = _FakeAnnotation.made
    assert ann.name == "on" and not ann.open


def test_disabled_span_imports_no_jax():
    env = {k: v for k, v in os.environ.items() if k != trace.ENV_VAR}
    src = Path(__file__).resolve().parents[1] / "src"
    env["PYTHONPATH"] = str(src)
    code = ("import sys\n"
            "from repro.obs import trace\n"
            "assert trace.span('x') is trace.NULL_SPAN\n"
            "with trace.span('x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=60)


def test_no_program_span_is_named_like_the_harness():
    """``bench.*`` names the benchmark harness's own annotations."""
    src = Path(__file__).resolve().parents[1] / "src"
    pat = re.compile(r"span\(\s*f?[\"']bench\.")
    hits = [str(p) for p in src.rglob("*.py") if pat.search(p.read_text())]
    assert hits == []


# ---------------------------------------------------------------------------
# the grid's host buckets: where each span nests
# ---------------------------------------------------------------------------

def _inside(child, parent) -> bool:
    return (child.tid == parent.tid and parent.ts <= child.ts
            and child.ts + child.dur <= parent.ts + parent.dur)


def _parents(ev, events, name):
    return [p for p in events if p.name == name and _inside(ev, p)]


@pytest.mark.parametrize("recode", [None, "naive"])
def test_grid_host_spans_nest(recode):
    from repro.kernels import comefa_sim

    trace.configure(enabled=True)
    rng = np.random.default_rng(3)
    g, k, n, wb, xb = 2, 48, 200, 3, 4
    w = rng.integers(0, 1 << wb, size=(g, k, n))
    x = rng.integers(0, 1 << xb, size=(g, k))
    y = comefa_sim.comefa_gemv_batched(w, x, w_bits=wb, x_bits=xb,
                                       acc_bits=16, recode=recode,
                                       engine="packed")
    assert np.array_equal(y, np.einsum("gkn,gk->gn", w, x))
    evs = [e for e in trace.get_tracer().events()
           if e.track == trace.WALL_TRACK]
    named = {}
    for e in evs:
        named.setdefault(e.name, []).append(e)
    kernel, = named["kernel.gemv_batched"]
    dispatch = "grid.run_per_slot" if recode else "grid.dispatch"
    n_dispatch = len(named[dispatch])
    assert n_dispatch >= 2            # a later tile's placement syncs
    # one placement span per tile, inside the kernel's span
    assert len(named["kernel.place"]) == n_dispatch
    assert all(_inside(e, kernel) for e in named["kernel.place"])
    # the final extract closes after the kernel's span
    extract, = named["kernel.extract"]
    assert extract.ts >= kernel.ts + kernel.dur
    # the first dispatch uploads the fresh state, each later one the state
    # its tile's placement synced back
    assert len(named["grid.upload"]) == n_dispatch
    for name in ("grid.upload", "grid.program_upload"):
        assert all(len(_parents(e, evs, dispatch)) == 1
                   for e in named[name])
    assert len(named["grid.program_upload"]) == n_dispatch
    if recode:
        assert len(named["grid.stack"]) == n_dispatch
        assert all(_parents(e, evs, dispatch) for e in named["grid.stack"])
    else:
        assert "grid.stack" not in named
    # a host sync in every tile's placement but the first, and one in the
    # extract; the wait for the device sits inside each
    syncs = named["grid.host_sync"]
    assert len(syncs) == n_dispatch
    assert sum(1 for s in syncs if _parents(s, evs, "kernel.place")) == \
        n_dispatch - 1
    assert _parents(syncs[-1], evs, "kernel.extract")
    assert len(named["grid.wait"]) == len(syncs)
    assert all(len(_parents(e, evs, "grid.host_sync")) == 1
               for e in named["grid.wait"])


def test_host_sync_waits_apart_only_when_traced(monkeypatch):
    from repro.core.comefa import grid as grid_mod

    waits = []
    real = grid_mod.jax.block_until_ready
    monkeypatch.setattr(grid_mod.jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    grid = ComefaGrid(2, n_blocks=1, engine="packed")
    for traced in (False, True):
        trace.configure(enabled=traced)
        grid.run(_mul_prog())
        grid.mem                              # noqa: B018 - the sync
    assert len(waits) == 1
    names = [e.name for e in trace.get_tracer().events()
             if e.name in ("grid.wait", "grid.host_sync")]
    assert names == ["grid.wait", "grid.host_sync"]


# ---------------------------------------------------------------------------
# bytes across the host boundary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["array", "grid"])
def test_transfer_bytes_are_the_packed_state(kind, monkeypatch):
    """Packed state is uint32 ``[G, nb, 128, 5]`` plus carry and mask
    ``[G, nb, 5]``: 4 * G * nb * (128*5 + 2*5) bytes each way."""
    monkeypatch.setattr(block, "_DEVICE_MAT_CACHE", {})
    nb, g = 2, 3
    if kind == "grid":
        target = ComefaGrid(g, n_blocks=nb, engine="packed")
        first = target.slot(0)
    else:
        g = 1
        target = first = ComefaArray(n_blocks=nb, engine="packed")
    state = 4 * g * nb * (128 * 5 + 2 * 5)
    prog = _mul_prog()
    a = np.arange(nb * 160).reshape(nb, 160) % (1 << BITS)
    layout.place(first, a, 0, BITS)
    tb = metrics.counter("comefa.transfer_bytes")

    def moved(direction, what):
        return tb.value(kind=kind, dir=direction, what=what)

    target.run(prog)
    assert moved("h2d", "state") == state
    assert moved("h2d", "program") == block.encoded(prog).nbytes
    assert moved("d2h", "state") == 0
    layout.extract(first, 2 * BITS, 2 * BITS)
    assert moved("d2h", "state") == state
    target.run(prog)                          # re-upload; program cached
    target.run(prog)                          # state stays on the device
    assert moved("h2d", "state") == 2 * state
    assert moved("h2d", "program") == block.encoded(prog).nbytes
    other = "grid" if kind == "array" else "array"
    assert not any(dict(key).get("kind") == other for key in tb.series())
