"""TPC-H Query 6 on a `ComefaGrid`: a predicated scan-filter-aggregate.

    SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem
    WHERE l_shipdate >= DATE AND l_shipdate < DATE + 1 year
      AND l_discount BETWEEN DISCOUNT - 0.01 AND DISCOUNT + 0.01
      AND l_quantity < QUANTITY

The four columns Q6 reads are unsigned integers (`COLUMNS`): shipdate in
days since 1992-01-01, discount in hundredths, quantity, and
extendedprice in cents, so the revenue is an exact integer in units of
1/10,000 dollar.  They are stored the CoMeFa way (paper Sec. III-E): row
``base + i`` of a RAM holds bit i of a value in each of its 160 lanes,
one table row per lane.  A *fill* is one table row in every lane of the
grid; the table lives on the device as one engine-format plane array per
fill (`pack_table`), standing for the FPGA board's DRAM.

A query streams the table through the grid one fill at a time: each fill
is written into the data rows on the device (`ComefaGrid.write_rows`),
then one program (`fill_program`) runs on every lane:

  * five compares against the query's constants, each the carry-out of
    an OOOR add of ``2^n - t`` (``x >= t``), streamed bit-serially
    (`program.add_ext_stream`) so one symbolic program serves every
    parameter set and is specialised once per set;
  * the flags ANDed into one keep flag;
  * per discount bit i: mask <- disc[i] AND keep, then a mask-predicated
    ``acc += price << i`` (`program.add_into`), so the accumulator sums
    price * discount over the lane's kept rows.

After the last fill one program (`readout_program`) reduces each 32-lane
group's accumulator into its first lane (`program.reduce_tree`), copies
the partial sums out and zeroes the accumulator for the next query; the
five partial sums of every RAM are read back once and added on the host.
"""
from __future__ import annotations

import datetime
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.comefa import program
from ..core.comefa.ir import Program, StreamedOperand, specialize_streams
from ..core.comefa.isa import (Instr, N_COLS, PRED_MASK, TT_A_ANDN_B,
                               TT_AND)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace

_KERNEL_CYCLES = obs_metrics.counter("comefa.kernel_cycles")
_FILLS = obs_metrics.counter("scan.fills")
_ROWS = obs_metrics.counter("scan.rows")

# (column, bits), in the order of the data rows
COLUMNS = (("shipdate", 12), ("discount", 4), ("quantity", 6),
           ("price", 24))
EPOCH = datetime.date(1992, 1, 1)      # shipdate 0
MAX_DISCOUNT = 10                      # hundredths (spec clause 4.2.3)
MAX_PRICE = 10_495_000                 # cents: 50 x the largest retail price

# -- row map ------------------------------------------------------------------
ACC_BITS = 34                 # > log2(163 fills x 10 x MAX_PRICE)
REDUCE_STEPS = 5              # 32-lane groups: 5 partial sums per RAM
GROUP = 1 << REDUCE_STEPS
SUM_BITS = ACC_BITS + REDUCE_STEPS
ACC = tuple(range(ACC_BITS))                       # rows 0-33
SUM = tuple(range(SUM_BITS))                       # 0-38: acc + reduce growth
DATA_BASE = SUM_BITS                               # 39
_rows: Dict[str, Tuple[int, ...]] = {}
_base = DATA_BASE
for _name, _bits in COLUMNS:
    _rows[_name] = tuple(range(_base, _base + _bits))
    _base += _bits
SHIP, DISC, QTY, PRICE = (_rows[c] for c, _ in COLUMNS)  # rows 39-84
DATA_BITS = _base - DATA_BASE                      # 46
GE_LO, GE_HI, GE_DLO, GE_DHI, GE_Q, KEEP, SINK = range(_base, _base + 7)  # 85-91
SCRATCH = tuple(range(DATA_BASE, DATA_BASE + SUM_BITS - 1))  # data, dead
OUT = tuple(range(DATA_BASE, DATA_BASE + SUM_BITS))        # rows 39-77
MAX_FILLS = ((1 << ACC_BITS) - 1) // (MAX_DISCOUNT * MAX_PRICE)

Params = Tuple[int, int, int]   # (year of DATE, DISCOUNT in hundredths, QUANTITY)


def date_bounds(year: int) -> Tuple[int, int]:
    """[DATE, DATE + 1 year) as shipdate codes, DATE = January 1 of `year`."""
    return ((datetime.date(year, 1, 1) - EPOCH).days,
            (datetime.date(year + 1, 1, 1) - EPOCH).days)


# the five ``x >= t`` compares: shipdate >= DATE, shipdate >= DATE + 1
# year, discount >= D - 1, discount >= D + 2, quantity >= Q
_COMPARES = ((SHIP, GE_LO), (SHIP, GE_HI), (DISC, GE_DLO), (DISC, GE_DHI),
             (QTY, GE_Q))


def stream_values(params: Params) -> List[int]:
    """The streamed addend of each compare: ``x + (2^n - t)`` carries out
    exactly when ``x >= t`` (n-bit x, 0 < t < 2^n)."""
    year, d, q = params
    out = []
    for t, (rows, _) in zip((*date_bounds(year), d - 1, d + 2, q),
                            _COMPARES):
        n = len(rows)
        if not 0 < t < (1 << n):
            raise ValueError(f"Q6 parameters {params}: a threshold of {t} "
                             f"does not fit {n} bits")
        out.append((1 << n) - t)
    return out


@functools.lru_cache(maxsize=None)
def _fill_template() -> Program:
    """The symbolic per-fill program: constants as streamed operands."""
    prog = Program(name="q6_fill")
    for k, (rows, flag) in enumerate(_COMPARES):
        stream = StreamedOperand(k, len(rows), f"t{k}", digit_set="binary")
        # the sum bits are never read: they go to one sink row, and the
        # carry-out lands in the flag row
        prog += program.add_ext_stream(rows, stream,
                                       [SINK] * len(rows) + [flag])
    prog += program.logic2([GE_LO], [GE_HI], [KEEP], TT_A_ANDN_B)
    prog += program.logic2([KEEP], [GE_DLO], [KEEP], TT_AND)
    prog += program.logic2([KEEP], [GE_DHI], [KEEP], TT_A_ANDN_B)
    prog += program.logic2([KEEP], [GE_Q], [KEEP], TT_A_ANDN_B)
    for i, d in enumerate(DISC):
        prog.append(Instr(src1_row=d, src2_row=KEEP, truth_table=TT_AND,
                          m_en=1, c_rst=1))            # mask <- disc[i] & keep
        prog += program.add_into(ACC, PRICE, i, pred_sel=PRED_MASK)
    return prog.with_live_out(ACC)


@functools.lru_cache(maxsize=None)
def fill_program(params: Params) -> Program:
    """The per-fill program of one parameter set, specialised once."""
    prog = specialize_streams(_fill_template(), stream_values(params),
                              optimize=True)
    prog.name = "q6_fill"
    return prog


@functools.lru_cache(maxsize=None)
def readout_program() -> Program:
    """Reduce each 32-lane group into its first lane, copy the partial
    sums to `OUT` and zero the accumulator for the next query."""
    prog = program.reduce_tree(SUM, SCRATCH, ACC_BITS, REDUCE_STEPS)
    prog += program.copy_rows(SUM, OUT)
    prog += program.zero_rows(ACC)
    prog = prog.with_live_out(OUT + ACC).optimize()
    prog.name = "q6_readout"
    return prog


class Table:
    """A lineitem table on the device: one engine-format plane array
    ``[G, nb, DATA_BITS, lanes]`` per fill, and the row count."""

    def __init__(self, planes: Sequence, n_rows: int):
        self.planes = list(planes)
        self.n_rows = n_rows


@functools.lru_cache(maxsize=None)
def _packer(pack_rows, g: int, nb: int, k: int):
    """Jitted: k fills of host column values -> k engine-format planes."""
    def pack(*cols):
        planes = []
        for v, (_, n) in zip(cols, COLUMNS):
            v = v.astype(jnp.uint32).reshape(k, g, nb, 1, N_COLS)
            shifts = jnp.arange(n, dtype=jnp.uint32)[:, None]
            planes.append(pack_rows((v >> shifts) & 1))  # bits [k, g, nb, n, C]
        planes = jnp.concatenate(planes, axis=-2)
        return tuple(planes[j] for j in range(k))
    return jax.jit(pack)


def pack_table(engine, columns: Dict[str, np.ndarray], g: int, nb: int,
               fills_per_call: int = 8) -> Table:
    """Lay `columns` (equal-length unsigned arrays, by `COLUMNS` name)
    out on the device, row r in lane ``r % (g * nb * 160)`` of fill
    ``r // (g * nb * 160)``.  The last fill's empty lanes hold zeros
    (shipdate 0, discount 0), which add nothing under any parameters."""
    cols = [np.asarray(columns[name]) for name, _ in COLUMNS]
    n_rows = len(cols[0])
    top = {"discount": MAX_DISCOUNT, "price": MAX_PRICE}
    for (name, bits), v in zip(COLUMNS, cols):
        hi = top.get(name, (1 << bits) - 1)
        if len(v) != n_rows or v.min() < 0 or v.max() > hi:
            raise ValueError(f"column {name}: {n_rows} values in 0..{hi} "
                             "expected")
    lanes = g * nb * N_COLS
    n_fills = -(-n_rows // lanes)
    if n_fills > MAX_FILLS:
        raise ValueError(f"{n_fills} fills would overflow the "
                         f"{ACC_BITS}-bit accumulator (at most {MAX_FILLS})")
    k = min(fills_per_call, n_fills)
    pack = _packer(engine.pack_rows, g, nb, k)
    planes = []
    for f0 in range(0, n_fills, k):
        chunk = []
        for v in cols:
            c = v[f0 * lanes:(f0 + k) * lanes]
            if len(c) < k * lanes:
                c = np.concatenate([c, np.zeros(k * lanes - len(c), c.dtype)])
            chunk.append(c)
        out = pack(*chunk)[:n_fills - f0]
        # one chunk in flight: its host columns, not every chunk's, on
        # the device at once
        planes += jax.block_until_ready(out)
    return Table(planes, n_rows)


def revenue_of(heads: np.ndarray) -> int:
    """Sum of the partial sums: rows `OUT` at the first lane of each
    32-lane group (``[G, nb, SUM_BITS, 5]`` bits)."""
    weights = np.int64(1) << np.arange(SUM_BITS, dtype=np.int64)
    return int(np.einsum("gbrl,r->", heads.astype(np.int64), weights))


def run_query(grid, table: Table, params: Params) -> int:
    """One Q6 query over `table` on `grid`: the revenue in 1/10,000 dollar.

    Fills are dispatched with one queued behind the running one; the
    accumulator must be zero on entry (a fresh grid, or after a query)
    and is zero again on return.
    """
    fill = fill_program(tuple(params))
    year, discount, quantity = params
    with obs_trace.span("scan.query", year=year, discount=discount,
                        quantity=quantity):
        pending = None
        for planes in table.planes:
            with obs_trace.span("scan.fill"):
                grid.write_rows(DATA_BASE, planes)
                _KERNEL_CYCLES.inc(grid.run(fill), kernel="q6_scan")
                _FILLS.inc()
                if pending is not None:
                    jax.block_until_ready(pending)
                pending = grid.device_state
        _ROWS.inc(table.n_rows)
        with obs_trace.span("scan.readout"):
            _KERNEL_CYCLES.inc(grid.run(readout_program()), kernel="q6_scan")
            revenue = revenue_of(grid.read_rows(OUT[0], len(OUT), GROUP))
    return revenue
