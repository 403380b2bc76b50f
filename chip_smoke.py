"""Chip smoke test: the CoMeFa simulator and grid-executed serving on a TPU.

    PYTHONPATH=src python chip_smoke.py                # one chip
    PYTHONPATH=src python chip_smoke.py --four-chips   # sharded grid only

(The script puts ``src/`` on the path itself, so plain
``python chip_smoke.py`` from the checkout root works too.)

One process drives every phase; it exits non-zero unless JAX's first
device is a TPU, and never falls back to the CPU.

  * Phase A, simulator: a G=8 x nb=8 `ComefaGrid` runs the 16-bit
    `program.mul` as one shared program, then one `run_per_slot` dispatch
    of a different-width multiply per slot.  Each runs on the compiled
    Pallas engine, the packed-XLA scan and the uint8 reference scan; the
    three must agree bit for bit in mem/carry/mask, and the products must
    equal numpy's.
  * Phase B, serving: smollm-360m at its published widths (depth and
    traffic cut, each cut printed) serves seeded requests through
    `serve_continuous` with a `GridLinearExecutor` on the packed engine.
    Every projection is compared bit for bit with the int64 reference
    twin, and the tokens with a ``backend="reference"`` run.  Grid
    dispatches on the compiled Pallas kernel must be above zero.
  * ``--four-chips`` (only this phase): a G=8 grid sharded over 4
    devices against the same grid on one device, bit for bit.

Times printed here are host wall-clock seconds, compile included; they
are not device metrics.  The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache, configs  # noqa: E402
from repro.core.comefa import ComefaGrid, layout, program  # noqa: E402
from repro.core.comefa.grid import grid_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.obs import metrics  # noqa: E402
from repro.serve import engine as serve_engine  # noqa: E402
from repro.serve.comefa_exec import GridLinearExecutor  # noqa: E402

ENGINES = ("pallas", "packed-xla", "reference")
G, NB, MUL_BITS = 8, 8, 16
SEED = 0
# phase B cuts: depth and traffic sized so the serve phase ends in a few
# minutes on one v5e (every k-chunk of every projection is one grid
# dispatch with a host round trip)
LAYERS, REQUESTS, PROMPT_LEN, DECODE_STEPS, SLOTS = 2, 4, 4, 2, 4


def log(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------------------
# per-phase accounting: host wall time, XLA compiles, dispatch counters
# ---------------------------------------------------------------------------

_COMPILES = {"backend_compile": 0, "persistent_cache_hit": 0}


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["backend_compile"] += 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILES["persistent_cache_hit"] += 1


def watch_compiles() -> None:
    """Count XLA compiles and persistent-cache hits from here on."""
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def require(ok: bool, what: str, detail=None) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what} ({detail})")


def _series(name: str) -> dict:
    return {",".join(f"{k}={v}" for k, v in labels): val
            for labels, val in metrics.counter(name).series().items()}


def run_phase(name: str, fn, *args, **kwargs) -> dict:
    """Run one phase with fresh counters; log and return its summary."""
    metrics.reset()
    before = dict(_COMPILES)
    t0 = time.perf_counter()
    result = fn(*args, **kwargs)
    summary = dict(
        phase=name, ok=True,
        host_wall_s=time.perf_counter() - t0,
        compiles={k: _COMPILES[k] - before[k] for k in _COMPILES},
        dispatches=_series("comefa.dispatches"),
        pallas_calls=_series("comefa.pallas_calls"),
        host_syncs=_series("comefa.host_syncs"),
        result=result)
    log(**summary)
    return summary


def _assert_state_equal(grids: dict, label: str) -> None:
    ref = grids["reference"]
    for name, grid in grids.items():
        for field in ("mem", "carry", "mask"):
            np.testing.assert_array_equal(
                getattr(grid, field), getattr(ref, field),
                err_msg=f"{label}: {name} {field} differs from reference")


# ---------------------------------------------------------------------------
# phase A: the simulator on every engine
# ---------------------------------------------------------------------------

def _mul_rows(n: int):
    return (list(range(n)), list(range(n, 2 * n)),
            list(range(2 * n, 4 * n)))


def _operand_grid(engine: str, rng_seed: int, widths, mesh=None):
    """G x NB grid; slot g holds random widths[g]-bit operands a, b in the
    rows `program.mul` of that width reads."""
    rng = np.random.default_rng(rng_seed)
    grid = ComefaGrid(G, n_blocks=NB, mesh=mesh, engine=engine)
    a, b = [], []
    for g, n in enumerate(widths):
        a.append(rng.integers(0, 1 << n, size=(NB, 160)))
        b.append(rng.integers(0, 1 << n, size=(NB, 160)))
        layout.place(grid.slot(g), a[g], 0, n)
        layout.place(grid.slot(g), b[g], n, n)
    return grid, a, b


def phase_simulator(seed: int) -> dict:
    out = {}
    # shared program: every slot runs one 16-bit multiply (Sec. III-D)
    mul16 = program.mul(*_mul_rows(MUL_BITS)).optimize()
    grids, t = {}, {}
    for name in ENGINES:
        grid, a, b = _operand_grid(name, seed, [MUL_BITS] * G)
        t0 = time.perf_counter()
        grid.run(mul16)
        grid.mem                         # sync: the dispatch has finished
        t[name] = time.perf_counter() - t0
        grids[name] = grid
    _assert_state_equal(grids, "shared mul16")
    for g in range(G):
        got = layout.extract(grids["pallas"].slot(g), 2 * MUL_BITS,
                             2 * MUL_BITS)
        np.testing.assert_array_equal(got, a[g] * b[g],
                                      err_msg=f"mul16 slot {g} product")
    out["shared_mul16"] = dict(cycles=grids["pallas"].cycles,
                               host_wall_s=t, bit_identical=True)

    # per-slot programs: slot g multiplies at width 9 + g (distinct
    # program lengths, so the stack pads), one run_per_slot dispatch
    widths = [9 + g for g in range(G)]
    progs = [program.mul(*_mul_rows(n)).optimize() for n in widths]
    grids, t = {}, {}
    for name in ENGINES:
        grid, a, b = _operand_grid(name, seed + 1, widths)
        t0 = time.perf_counter()
        counts = grid.run_per_slot(progs)
        grid.mem
        t[name] = time.perf_counter() - t0
        grids[name] = grid
    _assert_state_equal(grids, "per-slot mul")
    for g, n in enumerate(widths):
        got = layout.extract(grids["pallas"].slot(g), 2 * n, 2 * n)
        np.testing.assert_array_equal(got, a[g] * b[g],
                                      err_msg=f"mul{n} slot {g} product")
    out["per_slot_mul"] = dict(widths=widths, slot_cycles=counts,
                               host_wall_s=t, bit_identical=True)
    return out


# ---------------------------------------------------------------------------
# phase B: grid-executed serving at published widths
# ---------------------------------------------------------------------------

class _Twin:
    """Executor that runs every projection on the grid AND on the int64
    reference, requires them bit-equal, and returns the grid result."""

    def __init__(self, grid: GridLinearExecutor, ref: GridLinearExecutor):
        self.grid, self.ref = grid, ref
        self.calls = 0

    @property
    def active_mask(self):
        return self.grid.active_mask

    @active_mask.setter
    def active_mask(self, live):
        self.grid.active_mask = self.ref.active_mask = live

    def __call__(self, params, x2, bits: int):
        yg = np.asarray(self.grid(params, x2, bits))
        yr = np.asarray(self.ref(params, x2, bits))
        np.testing.assert_array_equal(
            yg, yr, err_msg=f"projection call {self.calls}: grid != ref")
        self.calls += 1
        return jax.numpy.asarray(yg)


def serve_config(layers: int):
    """smollm-360m at published widths, depth cut; returns (cfg, cuts)."""
    pub = configs.get("smollm-360m")
    cfg = dataclasses.replace(pub, quant_bits=4, scan_layers=False,
                              n_layers=layers)
    cuts = {"n_layers": f"{pub.n_layers} -> {layers}",
            "scan_layers": f"{pub.scan_layers} -> False (grid hook is eager)",
            "quant_bits": f"{pub.quant_bits} -> 4 (weights on the grid)",
            "weights": "random, seeded"}
    return cfg, cuts


def phase_serve(cfg, *, seed: int, n_requests: int, prompt_len: int,
                steps: int, slots: int) -> dict:
    params = lm.init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    reqs = [serve_engine.Request(rng.integers(0, cfg.vocab, prompt_len),
                                 steps) for _ in range(n_requests)]
    kw = dict(slots=slots, max_len=prompt_len + steps)

    t0 = time.perf_counter()
    ref_out = serve_engine.serve_continuous(
        params, reqs, cfg, executor=GridLinearExecutor(
            slots=slots, recode=None, backend="reference"), **kw)
    ref_s = time.perf_counter() - t0

    twin = _Twin(GridLinearExecutor(slots=slots, recode=None,
                                    engine="packed"),
                 GridLinearExecutor(slots=slots, recode=None,
                                    backend="reference"))
    stats: dict = {}
    t0 = time.perf_counter()
    grid_out = serve_engine.serve_continuous(params, reqs, cfg,
                                             executor=twin, stats=stats,
                                             **kw)
    grid_s = time.perf_counter() - t0
    for i, (g, r) in enumerate(zip(grid_out, ref_out)):
        np.testing.assert_array_equal(g, r, err_msg=f"request {i} tokens")
    n_tok = sum(len(o) for o in grid_out)
    return dict(tokens=[o.tolist() for o in grid_out],
                projections_bit_exact=twin.calls,
                batch_steps=stats["steps"], tokens_emitted=n_tok,
                grid_cycles=twin.grid.grid_cycles,
                grid_waves=_series("serve.grid_waves"),
                host_wall_s={"grid": grid_s, "reference": ref_s})


# ---------------------------------------------------------------------------
# --four-chips: the grid axis sharded across devices
# ---------------------------------------------------------------------------

def phase_sharded(seed: int, n_devices: int) -> dict:
    mul16 = program.mul(*_mul_rows(MUL_BITS)).optimize()
    widths = [9 + g for g in range(G)]
    progs = [program.mul(*_mul_rows(n)).optimize() for n in widths]
    mesh = grid_mesh(jax.devices()[:n_devices])
    one, _, _ = _operand_grid("packed", seed, widths)
    sharded, _, _ = _operand_grid("packed", seed, widths, mesh=mesh)
    t = {}
    for label, grid in (("one_device", one), ("sharded", sharded)):
        t0 = time.perf_counter()
        grid.run(mul16)
        grid.run_per_slot(progs)
        if label == "sharded":
            spread = len(grid._dev[0].sharding.device_set)
        grid.mem
        t[label] = time.perf_counter() - t0
    require(spread == n_devices, "sharded grid state spread", spread)
    for field in ("mem", "carry", "mask"):
        np.testing.assert_array_equal(getattr(sharded, field),
                                      getattr(one, field),
                                      err_msg=f"sharded {field}")
    return dict(slots=G, blocks=NB, devices=spread, bit_identical=True,
                host_wall_s=t)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded-grid phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    watch_compiles()
    log(device=device, compile_cache_dir=compile_cache.enable(),
        timing="host wall-clock seconds, compile included (not device "
               "metrics)")

    if args.four_chips:
        if device["count"] != 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{device['count']}", file=sys.stderr)
            return 1
        run_phase("sharded_grid", phase_sharded, SEED, 4)
    else:
        a = run_phase("simulator", phase_simulator, SEED)
        require(a["pallas_calls"].get("mode=interpret", 0) == 0
                and a["pallas_calls"].get("mode=compiled", 0) > 0,
                "phase A ran the compiled Pallas kernel", a["pallas_calls"])

        cfg, cuts = serve_config(LAYERS)
        log(phase="serve", config=dict(
            name=cfg.name, d_model=cfg.d_model, n_heads=cfg.n_heads,
            kv_heads=cfg.kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
            vocab=cfg.vocab, n_layers=cfg.n_layers, dtype=cfg.dtype),
            cuts=cuts, traffic=dict(
                requests=REQUESTS, prompt_len=PROMPT_LEN,
                decode_steps=DECODE_STEPS, slots=SLOTS))
        b = run_phase("serve", phase_serve, cfg, seed=SEED,
                      n_requests=REQUESTS, prompt_len=PROMPT_LEN,
                      steps=DECODE_STEPS, slots=SLOTS)
        require(b["dispatches"].get("engine=pallas,kind=grid", 0) > 0
                and b["pallas_calls"].get("mode=interpret", 0) == 0,
                "serve projections dispatched on the compiled Pallas "
                "kernel", (b["dispatches"], b["pallas_calls"]))
        require(b["result"]["grid_waves"].get("backend=grid", 0) > 0,
                "serve projections ran on the grid",
                b["result"]["grid_waves"])
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
