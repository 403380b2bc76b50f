"""A CoMeFa fleet: one FPGA's RAMs as a `ComefaGrid`.

The grid holds every RAM of the configuration (G FSM slices x nb blocks).
Set-up writes the seeded operands into the state (row i of an operand
holds bit i of every lane, the paper's transposed layout) and warms the
dispatch.  Each dispatch is a multiply-accumulate: ``p <- a * b`` (2n
bits), then ``acc <- acc + p`` modulo 2^(2n) - one shared program
(`ComefaGrid.run`) or one program per slice (`run_per_slot`).  The
accumulator makes the final state count every dispatch: after k of them
it holds k * a * b, so a window that skips, repeats or memoizes work
reads wrong.  The window repeats the dispatch with no host access,
keeping one queued behind the running one, and ends with the state ready
on the device.  After the window every lane's operands, product and
accumulator are read back and compared with the plain reference.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import jax
import numpy as np

import loadgen
from repro.core.comefa import ComefaGrid, isa, program
from repro.obs import trace as obs_trace


OPS = ("mac",)


def _rows(n: int):
    """Rows a multiply-accumulate of width n uses: a, b, product, acc."""
    return (list(range(n)), list(range(n, 2 * n)),
            list(range(2 * n, 4 * n)), list(range(4 * n, 6 * n)))


def mac(n: int):
    """``p <- a * b; acc <- (acc + p) mod 2^(2n)`` on n-bit operands."""
    a, b, p, acc = _rows(n)
    return (program.mul(a, b, p) + program.add_into(acc, p, 0)).optimize()


class System:
    def __init__(self, *, config: Dict, mix: Dict, seed: int,
                 rehearse: bool, reference):
        self.cfg = dict(config)
        if rehearse:
            self.cfg.update(config["rehearsal"])
        self.mix = mix
        self.seed = seed
        self.ref = reference
        self.engine = "pallas" if rehearse else self.cfg["engine"]
        self.expect_mode = "interpret" if rehearse else "compiled"
        self.g = self.cfg["fsm_slices"]
        self.nb = self.cfg["blocks_per_slice"]
        self.lanes = self.cfg["ram_cols"]
        if not rehearse and self.g * self.nb != self.cfg["brams"]:
            raise SystemExit("bench: fsm_slices x blocks_per_slice is not "
                             "the configuration's RAM count")
        self.per_slot = bool(mix["per_slot"])
        if mix["op"] not in OPS:
            raise SystemExit(f"bench: fleet traffic op {mix['op']!r} is not "
                             f"one of {OPS}")
        self.dispatched = 0          # every dispatch, set-up's included

    def setup(self, annotate: bool) -> None:
        self.annotate = annotate
        self.widths = loadgen.grid_widths(self.mix, self.seed, self.g)
        self.a, self.b = loadgen.grid_operands(self.seed, self.widths,
                                               self.nb, self.lanes)
        self.grid = ComefaGrid(self.g, n_blocks=self.nb, engine=self.engine)
        mem = self.grid.mem
        for g, n in enumerate(self.widths):
            for i in range(n):
                mem[g, :, i, :] = (self.a[g] >> i) & 1
                mem[g, :, n + i, :] = (self.b[g] >> i) & 1
        if self.per_slot:
            self.progs = [mac(int(n)) for n in self.widths]
            self.own = [p.cycles for p in self.progs]
        else:
            self.prog = mac(int(self.widths[0]))
            self.own = [self.prog.cycles] * self.g
        self._dispatch()                 # compiles, and uploads the state
        jax.block_until_ready(jax.live_arrays())

    def _dispatch(self):
        self.dispatched += 1
        if self.per_slot:
            return self.grid.run_per_slot(self.progs)
        return self.grid.run(self.prog)

    def window(self, t0: float, seconds: float) -> Dict:
        deadline = t0 + seconds
        ann = (jax.profiler.TraceAnnotation if self.annotate
               else lambda _: contextlib.nullcontext())
        n = 0
        pending = None
        with obs_trace.span("bench.window_start"):
            pass
        with ann("bench.dispatch_loop"):
            while True:
                with ann("bench.dispatch"):
                    self._dispatch()
                n += 1
                if pending is not None:
                    # one dispatch stays queued behind the running one
                    jax.block_until_ready(pending)
                # drop the last list first: listed while it is held, every
                # earlier state would stay alive, one more per dispatch
                pending = None
                pending = jax.live_arrays()
                if time.perf_counter() >= deadline:
                    break
            jax.block_until_ready(jax.live_arrays())
        window_s = time.perf_counter() - t0
        with obs_trace.span("bench.window_end"):
            pass
        lane_cycles = n * sum(self.own) * self.nb * self.lanes
        self.result = dict(window_s=window_s, dispatches=n,
                           lane_cycles=lane_cycles,
                           rate=lane_cycles / window_s)
        return self.result

    def end_to_end(self) -> Dict[str, float]:
        return {"sim_lane_cycles_per_s": self.result["rate"]}

    def attempted(self) -> int:
        return self.result["dispatches"]

    def facts(self) -> Dict:
        return dict(dispatches=self.result["dispatches"], slots=self.g,
                    n_blocks=self.nb, own_cycles=list(self.own),
                    per_slot=self.per_slot, n_fields=isa.N_ENGINE_FIELDS)

    def span_window(self, events) -> tuple:
        """The window in the obs tracer's clock (microseconds)."""
        return (max(e.ts for e in events if e.name == "bench.window_start"),
                max(e.ts for e in events if e.name == "bench.window_end"))

    def free(self) -> None:
        """Read the state back; nothing else of the program is needed."""
        self.mem = np.array(self.grid.mem)
        self.grid = None

    def check(self, control: bool) -> Dict:
        """Lanes whose operands, product or accumulator differ from the
        reference after every dispatch made.  With `control` the
        reference's float32 products stand in for what the grid left."""
        k = self.dispatched
        bad = 0
        for g, n in enumerate(self.widths):
            n = int(n)
            want = self.ref.state(self.a[g], self.b[g], n, k)
            if control:
                got = self.ref.state(self.a[g], self.b[g], n, k,
                                     products=self.ref.products_float32)
            else:
                got = {name: self.ref.read_rows(self.mem[g], base, width)
                       for name, (base, width) in
                       self.ref.fields(n).items()}
            bad += int(sum(np.sum(got[f] != want[f]) for f in want))
        checks = {"product_mismatch": dict(
            value=bad, limit=self.cfg["limits"]["product_mismatch"],
            bad=bad)}
        return dict(checks=checks, info={"dispatches": k})

    def validity(self, counters: Dict[str, float]) -> List[str]:
        bad = []
        grid = sum(v for k, v in counters.items()
                   if k.startswith("comefa.dispatches{") and "kind=grid" in k)
        pallas = sum(v for k, v in counters.items()
                     if k.startswith("comefa.dispatches{")
                     and "kind=grid" in k and "engine=pallas" in k)
        calls = sum(v for k, v in counters.items()
                    if k.startswith("comefa.pallas_calls{"))
        mode = counters.get(f"comefa.pallas_calls{{mode={self.expect_mode}}}",
                            0)
        if grid <= 0:
            bad.append("the window made no grid dispatch")
        if pallas != grid or mode != calls:
            bad.append(f"a grid dispatch ran off engine=pallas / "
                       f"mode={self.expect_mode}")
        return bad
