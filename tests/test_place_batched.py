"""Device-side chunk placement and read-out of the batched GEMV kernels.

`comefa_gemv_batched` builds each tile's weight planes (and, on the
broadcast path, each slot's activation planes) on the device, writes them
into the stacked grid state with one `ComefaGrid.write_row_ranges` per
tile, and reads every slot's accumulator back with one `read_rows`.  The
oracle written out here is the per-slot, per-element `layout.place` /
`layout.extract` loop those writes replace: the grid state, read back
through ``grid.mem`` after the writes, must match it byte for byte after
every tile, starting from random bits so that a row written too many or
too few shows.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comefa import ComefaGrid, N_COLS, isa, layout, schedule
from repro.kernels import comefa_sim
from repro.obs import metrics

ACC_BITS = 20
ENGINES = ["reference", "packed"]


def _oracle_place(grid, w, x, plan, tile, x_rows):
    """The per-slot, per-element loop: pad each weight row to whole
    blocks and `layout.place` it, then broadcast x[g, j] over every lane."""
    nb = plan.n_blocks
    pad = nb * N_COLS - w.shape[2]
    buf = plan.buffers[tile.buffer]
    for g in range(grid.g):
        slot = grid.slot(g)
        for j_local, j in enumerate(range(tile.k_start, tile.k_end)):
            wj = np.pad(w[g, j], (0, pad)).reshape(nb, N_COLS)
            rows = buf.weight_rows(j_local, plan.w_bits)
            layout.place(slot, wj, rows.base, plan.w_bits)
            if x_rows is not None:
                layout.place(slot, np.full(N_COLS, int(x[g, j])),
                             x_rows[j_local].base, plan.x_bits)


def _plan(path, w_bits, x_bits):
    if path == "broadcast":
        k_tile = comefa_sim.gemv_batched_k_tile(w_bits, x_bits, ACC_BITS)
        reserve = False
    else:
        k_tile = schedule.gemv_k_tile(w_bits, ACC_BITS, reserve_neg=True)
        reserve = True
    k = 2 * k_tile + 1                  # three tiles, the last one short
    plan = schedule.cached_plan_gemv(k, 330, w_bits, x_bits, ACC_BITS,
                                     k_tile=k_tile, reserve_neg=reserve)
    x_rows = (comefa_sim._gemv_batched_layout(plan)
              if path == "broadcast" else None)
    return plan, x_rows


def _random_grids(g, plan, engine, seed):
    rng = np.random.default_rng(seed)
    start = rng.integers(0, 2, size=(g, plan.n_blocks, 128, N_COLS),
                         dtype=np.uint8)
    got = ComefaGrid(g, n_blocks=plan.n_blocks, engine=engine)
    want = ComefaGrid(g, n_blocks=plan.n_blocks)
    got.mem = start.copy()
    want.mem = start.copy()
    return got, want


def _place_and_compare(got, want, weights, w, x, plan, x_rows):
    """Place every tile both ways; the states agree after each one."""
    w_planes = weights.planes(plan, got.engine)
    x_planes = (comefa_sim._x_planes(x, plan, got.engine)
                if x_rows is not None else None)
    x_base = x_rows[0].base if x_rows is not None else 0
    for tile in plan.tiles():
        comefa_sim._place_tile(got, plan, tile, w_planes, x_planes, x_base)
        _oracle_place(want, w, x, plan, tile, x_rows)
        np.testing.assert_array_equal(got.mem, want.mem)
    assert got.mem.dtype == np.uint8


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("path", ["broadcast", "per_slot"])
@pytest.mark.parametrize("x_bits", [4, 8])
@pytest.mark.parametrize("w_bits", [2, 4, 8])
@pytest.mark.parametrize("g", [1, 3, 4])
def test_chunk_placement_matches_per_element_loop(g, w_bits, x_bits, path,
                                                  engine):
    plan, x_rows = _plan(path, w_bits, x_bits)
    tiles = plan.tiles()
    assert plan.n_blocks == 3 and plan.n % N_COLS       # padding lanes
    assert tiles[-1].n_elems < plan.k_tile               # a short last tile
    assert {t.buffer for t in tiles} == {0, 1}
    seed = 1000 * g + 10 * w_bits + x_bits
    rng = np.random.default_rng(seed)
    # out-of-range and negative weights pin the same low-bit truncation
    w = rng.integers(-(1 << w_bits), 2 << w_bits, size=(g, plan.k, plan.n))
    x = rng.integers(0, 1 << x_bits, size=(g, plan.k))
    got, want = _random_grids(g, plan, engine, seed)
    _place_and_compare(got, want, comefa_sim.GemvWeights(w), w, x, plan,
                       x_rows)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("path", ["broadcast", "per_slot"])
def test_shared_weights_place_as_a_copy_per_slot(path, engine):
    """``[k, n]`` weights, planes built once without the slot axis and
    broadcast at the write, place as the old per-slot copy did."""
    plan, x_rows = _plan(path, 4, 8)
    g = 3
    rng = np.random.default_rng(17)
    w = rng.integers(0, 16, size=(plan.k, plan.n))
    x = rng.integers(0, 256, size=(g, plan.k))
    got, want = _random_grids(g, plan, engine, 17)
    weights = comefa_sim.GemvWeights(w)
    _place_and_compare(got, want, weights, np.broadcast_to(w, (g,) + w.shape),
                       x, plan, x_rows)
    assert weights.planes(plan, got.engine)[0].shape[:-1] == \
        (plan.n_blocks, plan.k_tile * 4)


@pytest.mark.parametrize("bad", [16, -1])
def test_x_placement_checks_range(bad):
    plan, _ = _plan("broadcast", 4, 4)
    engine = ComefaGrid(1).engine
    x = np.zeros((2, plan.k), dtype=np.int64)
    comefa_sim._x_planes(x, plan, engine)
    x[1, plan.k - 1] = bad
    with pytest.raises(ValueError):
        comefa_sim._x_planes(x, plan, engine)
    w = np.zeros((plan.k, plan.n), dtype=np.int64)
    with pytest.raises(ValueError):
        comefa_sim.comefa_gemv_batched(w, x, w_bits=4, x_bits=4,
                                       acc_bits=ACC_BITS)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("g,n_blocks,acc_bits,n", [
    (1, 1, 20, 160), (3, 2, 24, 170), (4, 3, 32, 330), (4, 6, 22, 960)])
def test_extract_batched_matches_per_slot_extract(g, n_blocks, acc_bits, n,
                                                  engine):
    """The accumulator read from the device state with `read_rows` is the
    per-slot `layout.extract` loop's, and the state is not synced."""
    rng = np.random.default_rng(n + acc_bits)
    start = rng.integers(0, 2, size=(g, n_blocks, 128, N_COLS),
                         dtype=np.uint8)
    host = ComefaGrid(g, n_blocks=n_blocks)
    host.mem = start.copy()
    grid = ComefaGrid(g, n_blocks=n_blocks, engine=engine)
    grid.mem = start.copy()
    # rewriting row 0 with its own bits puts the state on the device
    grid.write_row_ranges([(0, grid.engine.pack_rows(
        jnp.asarray(start[:, :, :1])))])
    base = 60
    want = np.stack([layout.extract(host.slot(s), base, acc_bits)
                     .reshape(-1)[:n] for s in range(g)])
    got = comefa_sim._extract_batched(grid, base, acc_bits, n)
    assert grid.host_syncs == 0 and grid.device_state is not None
    assert got.dtype == np.int64 and got.shape == (g, n)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("engine", ENGINES)
def test_resident_planes_are_reused_across_calls(engine):
    """Planes kept in a `GemvWeights` serve a second call with other
    activations: one build, one reuse, both results exact, no host sync."""
    rng = np.random.default_rng(23)
    g, k, n, wb, xb = 3, 23, 170, 4, 8
    weights = comefa_sim.GemvWeights(rng.integers(0, 1 << wb, size=(k, n)))
    planes = metrics.counter("comefa.weight_planes")
    for _ in range(2):
        x = rng.integers(0, 1 << xb, size=(g, k))
        got = comefa_sim.comefa_gemv_batched(weights, x, w_bits=wb,
                                             x_bits=xb, acc_bits=ACC_BITS,
                                             engine=engine)
        np.testing.assert_array_equal(got, x @ weights.w)
    assert planes.value(event="build") == 1
    assert planes.value(event="reuse") == 1
    assert metrics.counter("comefa.host_syncs").value(kind="grid") == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_write_row_ranges_broadcasts_each_range(engine):
    """Planes without the slot axis, or with one block, broadcast; each
    range lands as `write_rows` of the full shape would put it."""
    g, nb = 3, 2
    rng = np.random.default_rng(29)
    start = rng.integers(0, 2, size=(g, nb, 128, N_COLS), dtype=np.uint8)
    shared = rng.integers(0, 2, size=(nb, 5, N_COLS), dtype=np.uint8)
    per_slot = rng.integers(0, 2, size=(g, 1, 3, N_COLS), dtype=np.uint8)
    grid = ComefaGrid(g, n_blocks=nb, engine=engine)
    grid.mem = start.copy()
    pack = grid.engine.pack_rows
    grid.write_row_ranges([(7, pack(jnp.asarray(shared))),
                           (40, pack(jnp.asarray(per_slot)))])
    want = start.copy()
    want[:, :, 7:12] = shared
    want[:, :, 40:43] = per_slot
    np.testing.assert_array_equal(grid.mem, want)
    for base, bits in [(isa.USABLE_ROWS - 2, shared),      # reserved rows
                       (0, np.zeros((2, nb, 1, N_COLS), np.uint8))]:  # G
        with pytest.raises(ValueError):
            grid.write_row_ranges([(base, pack(jnp.asarray(bits)))])
