"""TPC-H Q6 streamed through a CoMeFa fleet (`repro.kernels.comefa_scan`).

Set-up generates the four lineitem columns Q6 reads from the seed, under
the rules of spec clause 4.2.3 (`lineitem`), lays them out on the device
as one plane array per fill of the grid's lanes (the FPGA board's DRAM),
and runs one query for every distinct program length of the parameter
sets the traffic can draw.  The window runs queries back to back, each
with the next parameters of the seeded stream (clause 2.4.6.3); a query
writes every fill into the RAMs on the device and runs the fill program
with one dispatch queued behind the running one, then reduces in the
grid and reads the partial sums back once.  The window ends at the first
query boundary after the deadline, with every query's revenue on the
host.  After it, each query's revenue is compared with the plain
reference, each distinct parameter set computed once.
"""
from __future__ import annotations

import contextlib
import datetime
import time
from typing import Dict, List

import jax
import numpy as np

import loadgen
from repro.core.comefa import ComefaGrid, isa
from repro.kernels import comefa_scan
from repro.obs import trace as obs_trace

EPOCH = datetime.date(1992, 1, 1)
# clause 4.2.3: O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151 days]
LAST_ORDERDATE = (datetime.date(1998, 12, 31) - EPOCH).days - 151


def lineitem(seed: int, n_rows: int, scale_factor: int
             ) -> Dict[str, np.ndarray]:
    """The columns Q6 reads of `n_rows` lineitem rows (clause 4.2.3):

    orders of 1-7 lineitems, O_ORDERDATE uniform over [1992-01-01,
    1998-12-31 - 151 days], L_SHIPDATE = O_ORDERDATE + U[1, 121] days;
    L_QUANTITY U[1, 50]; L_DISCOUNT U[0.00, 0.10]; L_EXTENDEDPRICE =
    L_QUANTITY x P_RETAILPRICE(L_PARTKEY), L_PARTKEY U[1, SF x 200,000],
    P_RETAILPRICE = (90000 + ((key / 10) mod 20001) + 100 (key mod 1000))
    / 100.  As codes: days since 1992-01-01, hundredths, cents.
    """
    r = loadgen.rng(seed, "lineitem")
    n_orders = n_rows // 4 + n_rows // 8 + 64     # 1.5x the rows, on average
    per_order = r.integers(1, 8, n_orders)
    assert per_order.sum() >= n_rows
    orderdate = r.integers(0, LAST_ORDERDATE + 1, n_orders, dtype=np.uint16)
    ship = np.repeat(orderdate, per_order)[:n_rows]
    ship += r.integers(1, 122, n_rows, dtype=np.uint16)
    quantity = r.integers(1, 51, n_rows, dtype=np.uint8)
    discount = r.integers(0, 11, n_rows, dtype=np.uint8)
    key = r.integers(1, scale_factor * 200_000 + 1, n_rows, dtype=np.int32)
    retail = 90_000 + (key // 10) % 20_001 + 100 * (key % 1_000)   # cents
    price = (retail * quantity).astype(np.uint32)
    return {"shipdate": ship, "discount": discount, "quantity": quantity,
            "price": price}


def _choices(dist: Dict) -> List[int]:
    assert dist["dist"] == "uniform_int", dist
    return list(range(int(dist["lo"]), int(dist["hi"]) + 1))


def parameter_sets(mix: Dict) -> List[tuple]:
    """Every (year, discount, quantity) the traffic can draw."""
    return [(y, d, q) for y in _choices(mix["year"])
            for d in _choices(mix["discount"])
            for q in _choices(mix["quantity"])]


def query_stream(mix: Dict, seed: int) -> List[tuple]:
    """The closed loop's queries, in order: each parameter uniform over
    its range, independently (clause 2.4.6.3)."""
    r = loadgen.rng(seed, "q6")
    n = int(mix["queries"])
    cols = [r.choice(_choices(mix[k]), size=n)
            for k in ("year", "discount", "quantity")]
    return [tuple(int(c[i]) for c in cols) for i in range(n)]


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
        if not rehearse and self.g * self.nb != self.cfg["brams"]:
            raise SystemExit("bench: fsm_slices x blocks_per_slice is not "
                             "the configuration's RAM count")
        if mix["kind"] != "q6_params" or mix["loop"] != "closed":
            raise SystemExit("bench: scan traffic must be a closed loop of "
                             "q6_params")
        for name, bits in comefa_scan.COLUMNS:
            if self.cfg["columns"][name]["bits"] != bits:
                raise SystemExit(f"bench: column {name} is not {bits} bits")
        self.queries: List[tuple] = []     # (params, revenue), set-up's too

    def setup(self, annotate: bool) -> None:
        self.annotate = annotate
        self.cols = lineitem(self.seed, self.cfg["rows"],
                             self.cfg["scale_factor"])
        self.stream = query_stream(self.mix, self.seed)
        self.grid = ComefaGrid(self.g, n_blocks=self.nb, engine=self.engine)
        self.table = comefa_scan.pack_table(self.grid.engine, self.cols,
                                            self.g, self.nb)
        self.n_fills = len(self.table.planes)
        by_length = {}
        for p in parameter_sets(self.mix):
            by_length.setdefault(len(comefa_scan.fill_program(p)), p)
        for p in by_length.values():     # compiles, and uploads the state
            self._query(p)
        jax.block_until_ready(jax.live_arrays())

    def _query(self, params: tuple) -> None:
        revenue = comefa_scan.run_query(self.grid, self.table, params)
        self.queries.append((params, revenue))

    def window(self, t0: float, seconds: float) -> Dict:
        deadline = t0 + seconds
        ann = (jax.profiler.TraceAnnotation if self.annotate
               else lambda _: contextlib.nullcontext())
        first = len(self.queries)
        n = 0
        with obs_trace.span("bench.window_start"):
            pass
        with ann("bench.query_loop"):
            while True:
                with ann("bench.query"):
                    self._query(self.stream[n % len(self.stream)])
                n += 1
                if time.perf_counter() >= deadline:
                    break
        window_s = time.perf_counter() - t0
        with obs_trace.span("bench.window_end"):
            pass
        fills = self.n_fills
        readout = len(comefa_scan.readout_program())
        cycles = sum(fills * len(comefa_scan.fill_program(p)) + readout
                     for p, _ in self.queries[first:])
        lane_cycles = cycles * self.g * self.nb * isa.N_COLS
        self.result = dict(window_s=window_s, queries=n,
                           dispatches=n * (fills + 1),
                           rate=lane_cycles / window_s,
                           queries_per_s=n / window_s,
                           rows_per_s=n * self.table.n_rows / window_s)
        return self.result

    def end_to_end(self) -> Dict[str, float]:
        return {"sim_lane_cycles_per_s": self.result["rate"]}

    def attempted(self) -> int:
        return self.result["queries"]

    def facts(self) -> Dict:
        return dict(dispatches=self.result["dispatches"], slots=self.g,
                    n_blocks=self.nb, per_slot=False,
                    n_fields=isa.N_ENGINE_FIELDS)

    def span_window(self, events) -> tuple:
        """The window in the obs tracer's clock (microseconds)."""
        return (max(e.ts for e in events if e.name == "bench.window_start"),
                max(e.ts for e in events if e.name == "bench.window_end"))

    def free(self) -> None:
        """The revenues are on the host; drop the grid and the table."""
        self.grid = None
        self.table = None

    def check(self, control: bool) -> Dict:
        """Queries whose revenue differs from the reference's.  With
        `control` the reference's float32 sums stand in for the grid's."""
        want = {}
        for p, _ in self.queries:
            if p not in want:
                want[p] = self.ref.revenue(self.cols, *p)
        if control:
            ctl = {p: self.ref.revenue_float32(self.cols, *p) for p in want}
            got = [(p, ctl[p]) for p, _ in self.queries]
        else:
            got = self.queries
        bad = sum(revenue != want[p] for p, revenue in got)
        checks = {"revenue_mismatch": dict(
            value=bad, limit=self.cfg["limits"]["revenue_mismatch"],
            bad=bad)}
        info = {"queries": len(self.queries),
                "distinct_parameter_sets": len(want),
                "fills_per_query": self.n_fills,
                "queries_per_s": self.result["queries_per_s"],
                "rows_per_s": self.result["rows_per_s"]}
        return dict(checks=checks, info=info)

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
