"""TPC-H Q6 as a streamed scan on a `ComefaGrid` (`kernels.comefa_scan`).

A tiny grid (2 slots x 2 blocks, 640 lanes) scans a table of 2,000 rows
in four fills, the last one partial.  Every engine's revenue equals the
int64 numpy reference exactly, for the specification's validation
parameters and for rows placed on every edge of every predicate.
`ComefaGrid.write_rows` is pinned byte-identical to the same rows placed
through ``mem``, with the rest of the state untouched and no host sync or
upload, and the query's spans and counters are checked.
"""
import numpy as np
import pytest

from repro.core.comefa import ComefaGrid, isa, verify
from repro.core.comefa.isa import N_COLS
from repro.kernels import comefa_scan as scan
from repro.kernels.ref import q6_revenue_ref
from repro.obs import metrics, trace

G, NB = 2, 2
N_ROWS = 2000                    # 3 full fills of 640 lanes and 80 rows
ENGINES = ["reference", "packed-xla", "pallas"]
VALIDATION = (1994, 6, 24)       # spec clause 2.4.6.4: 1994-01-01, 0.06, 24
ALL_PARAMS = [(y, d, q) for y in range(1993, 1998) for d in range(2, 10)
              for q in (24, 25)]


def _columns(params, seed=0):
    """Random rows in the clause 4.2.3 domains, with one row for every
    combination of predicate edges of `params` spread among them."""
    rng = np.random.default_rng(seed)
    cols = {"shipdate": rng.integers(1, 2527, N_ROWS),
            "discount": rng.integers(0, 11, N_ROWS),
            "quantity": rng.integers(1, 51, N_ROWS),
            "price": rng.integers(90_000, scan.MAX_PRICE + 1, N_ROWS)}
    year, d, q = params
    lo, hi = scan.date_bounds(year)
    edges = [(s, dd, qq) for s in (lo - 1, lo, hi - 1, hi)
             for dd in (d - 2, d - 1, d, d + 1, d + 2) for qq in (q - 1, q)
             if dd <= scan.MAX_DISCOUNT]
    at = rng.choice(N_ROWS, size=len(edges), replace=False)
    at[0] = N_ROWS - 1                         # one in the partial fill
    for r, (s, dd, qq) in zip(at, edges):
        cols["shipdate"][r], cols["discount"][r], cols["quantity"][r] = \
            s, dd, qq
    cols["price"][at[-1]] = scan.MAX_PRICE
    return cols


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("params", [VALIDATION, (1996, 2, 25),
                                    (1997, 9, 24)])
def test_revenue_matches_reference_exactly(engine, params):
    cols = _columns(params)
    grid = ComefaGrid(G, n_blocks=NB, engine=engine)
    table = scan.pack_table(grid.engine, cols, G, NB, fills_per_call=3)
    assert len(table.planes) == 4
    # back to back: the readout leaves the accumulator zero for the next
    for p in (params, VALIDATION):
        want = q6_revenue_ref(cols, *p)
        assert want > 0
        assert scan.run_query(grid, table, p) == want


def test_table_layout_is_row_per_lane():
    cols = _columns(VALIDATION, seed=1)
    grid = ComefaGrid(G, n_blocks=NB, engine="reference")
    table = scan.pack_table(grid.engine, cols, G, NB)
    planes = np.concatenate([np.asarray(p) for p in table.planes], axis=0)
    # fill f, slot g, block b, lane c holds row ((f*G + g)*NB + b)*160 + c
    bits = planes.reshape(len(table.planes), G, NB, scan.DATA_BITS, N_COLS)
    bits = bits.transpose(0, 1, 2, 4, 3).reshape(-1, scan.DATA_BITS)
    base = 0
    for name, n in scan.COLUMNS:
        v = (bits[:, base:base + n].astype(np.int64)
             << np.arange(n)).sum(axis=1)
        assert np.array_equal(v[:N_ROWS], cols[name]), name
        assert not v[N_ROWS:].any()             # padding lanes are zero
        base += n


def _random_grid(engine, seed):
    grid = ComefaGrid(G, n_blocks=NB, engine=engine)
    rng = np.random.default_rng(seed)
    mem = rng.integers(0, 2, size=grid.mem.shape).astype(np.uint8)
    mem[:, :, list(isa.RESERVED_ROWS)] = grid.mem[:, :, list(
        isa.RESERVED_ROWS)]
    grid.mem = mem
    grid.carry = rng.integers(0, 2, size=grid.carry.shape).astype(np.uint8)
    grid.mask = rng.integers(0, 2, size=grid.mask.shape).astype(np.uint8)
    return grid


@pytest.mark.parametrize("engine", ENGINES)
def test_write_rows_matches_placing_through_mem(engine):
    import jax.numpy as jnp
    base, n = 39, 46
    rng = np.random.default_rng(5)
    bits = rng.integers(0, 2, size=(G, NB, n, N_COLS)).astype(np.uint8)
    dev, host = _random_grid(engine, 1), _random_grid(engine, 1)
    dev.run(scan.fill_program(VALIDATION))     # state now on the device
    host.run(scan.fill_program(VALIDATION))
    syncs, puts = dev.host_syncs, dev.device_puts
    planes = dev.engine.pack_rows(jnp.asarray(bits))
    dev.write_rows(base, planes)
    assert (dev.host_syncs, dev.device_puts) == (syncs, puts)
    assert metrics.counter("comefa.transfer_bytes").value(
        kind="grid", dir="d2d", what="rows") == planes.nbytes
    # the rows alone, read back without a host sync, all lanes or some
    assert np.array_equal(dev.read_rows(base, n), bits)
    assert np.array_equal(dev.read_rows(base + 3, 5, 32),
                          bits[:, :, 3:8, ::32])
    assert dev.host_syncs == syncs
    before = host.mem.copy()
    host.mem[:, :, base:base + n] = bits
    assert np.array_equal(dev.mem, host.mem)
    assert np.array_equal(dev.carry, host.carry)
    assert np.array_equal(dev.mask, host.mask)
    rest = np.ones(before.shape[2], bool)
    rest[base:base + n] = False
    assert np.array_equal(dev.mem[:, :, rest], before[:, :, rest])


def test_write_rows_keeps_the_reserved_rows():
    grid = ComefaGrid(G, n_blocks=NB, engine="packed-xla")
    planes = grid.engine.pack_rows(
        np.ones((G, NB, 2, N_COLS), np.uint8))
    with pytest.raises(ValueError):
        grid.write_rows(isa.ROW_ZEROS - 1, planes)
    with pytest.raises(ValueError):
        grid.write_rows(0, planes[:1])


def test_out_of_domain_inputs_are_refused():
    cols = _columns(VALIDATION)
    cols["discount"][7] = scan.MAX_DISCOUNT + 1
    grid = ComefaGrid(G, n_blocks=NB, engine="packed-xla")
    with pytest.raises(ValueError):
        scan.pack_table(grid.engine, cols, G, NB)
    with pytest.raises(ValueError):
        scan.fill_program((1994, 14, 24))       # D + 2 needs 5 bits


def test_programs_verify_and_fit_the_rows():
    lengths = {len(scan.fill_program(p)) for p in ALL_PARAMS}
    # every parameter set specialises to a program of one length
    assert len(lengths) == 1 and lengths.pop() <= 200
    for prog in (scan.fill_program(VALIDATION), scan.readout_program()):
        diags = verify.verify_program(prog, n_blocks=NB, chain=False)
        assert not [d for d in diags if d.is_error], diags
        rows = {r for i in prog.instrs()
                for r in (i.src1_row, i.src2_row, i.dst_row)}
        written = verify.written_rows(prog.slots)
        assert max(rows) < isa.ROW_ZEROS and max(written) < isa.ROW_ZEROS
    # a flipped bit in rows 0-63 reaches the accumulator
    assert max(scan.ACC) < 64
    assert scan.MAX_FILLS >= 155


def test_query_spans_and_counters():
    cols = _columns(VALIDATION, seed=2)
    grid = ComefaGrid(G, n_blocks=NB, engine="packed-xla")
    table = scan.pack_table(grid.engine, cols, G, NB)
    scan.run_query(grid, table, VALIDATION)        # uploads the fresh state
    metrics.reset()
    trace.configure(enabled=True)
    assert scan.run_query(grid, table, VALIDATION) == \
        q6_revenue_ref(cols, *VALIDATION)
    evs = [e for e in trace.get_tracer().events()
           if e.track == trace.WALL_TRACK]
    named = {}
    for e in evs:
        named.setdefault(e.name, []).append(e)

    def inside(e, p):
        return p.ts <= e.ts and e.ts + e.dur <= p.ts + p.dur

    query, = named["scan.query"]
    assert query.attrs == {"year": 1994, "discount": 6, "quantity": 24}
    fills = named["scan.fill"]
    assert len(fills) == len(table.planes) == 4
    writes = named["grid.write_rows"]
    assert len(writes) == 4
    assert all(any(inside(w, f) for f in fills) for w in writes)
    readout, = named["scan.readout"]
    assert all(inside(e, query) for e in fills + [readout])
    assert "grid.upload" not in named and "grid.host_sync" not in named
    c = metrics.counter
    assert c("scan.fills").value() == 4
    assert c("scan.rows").value() == N_ROWS
    assert c("comefa.kernel_cycles").value(kernel="q6_scan") == \
        4 * len(scan.fill_program(VALIDATION)) + len(scan.readout_program())
    assert c("comefa.transfer_bytes").value(
        kind="grid", dir="d2d", what="rows") == sum(
            p.nbytes for p in table.planes)
    assert c("comefa.transfer_bytes").value(
        kind="grid", dir="d2h", what="state") == 0
    # one read per query: the partial sums' bits at each group's head lane
    assert c("comefa.transfer_bytes").value(
        kind="grid", dir="d2h", what="rows") == \
        G * NB * scan.SUM_BITS * (N_COLS // scan.GROUP)


def test_reference_and_program_agree_on_date_bounds():
    assert scan.date_bounds(1994) == (731, 1096)
    assert scan.date_bounds(1996) == (1461, 1827)    # 1996 is a leap year
    # the greatest shipdate code: 1998-12-31 - 151 days + 121 days
    assert scan.date_bounds(1998)[1] - 1 - 151 + 121 == 2526
