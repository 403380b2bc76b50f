"""Grid-wide chunk placement and read-out of the batched GEMV kernels.

`comefa_gemv_batched` writes each tile's weights (and, on the broadcast
path, each slot's activation bits) into the stacked grid state with one
vectorized write, and reads every slot's accumulator back with one read.
The oracle written out here is the per-slot, per-element `layout.place` /
`layout.extract` loop those writes replace: the grid state must match it
byte for byte after every tile, starting from random bits so that a row
written too many or too few shows.
"""
import numpy as np
import pytest

from repro.core.comefa import ComefaGrid, N_COLS, layout, schedule
from repro.kernels import comefa_sim

ACC_BITS = 20


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


@pytest.mark.parametrize("path", ["broadcast", "per_slot"])
@pytest.mark.parametrize("x_bits", [4, 8])
@pytest.mark.parametrize("w_bits", [2, 4, 8])
@pytest.mark.parametrize("g", [1, 3, 4])
def test_chunk_placement_matches_per_element_loop(g, w_bits, x_bits, path):
    plan, x_rows = _plan(path, w_bits, x_bits)
    tiles = plan.tiles()
    assert plan.n_blocks == 3 and plan.n % N_COLS       # padding lanes
    assert tiles[-1].n_elems < plan.k_tile               # a short last tile
    assert {t.buffer for t in tiles} == {0, 1}
    rng = np.random.default_rng(1000 * g + 10 * w_bits + x_bits)
    # out-of-range and negative weights pin the same low-bit truncation
    w = rng.integers(-(1 << w_bits), 2 << w_bits, size=(g, plan.k, plan.n))
    x = rng.integers(0, 1 << x_bits, size=(g, plan.k))
    start = rng.integers(0, 2, size=(g, plan.n_blocks, 128, N_COLS),
                         dtype=np.uint8)
    got = ComefaGrid(g, n_blocks=plan.n_blocks)
    want = ComefaGrid(g, n_blocks=plan.n_blocks)
    got.mem = start.copy()
    want.mem = start.copy()
    for tile in tiles:
        comefa_sim._place_weights(got.mem, w, plan, tile)
        if x_rows is not None:
            comefa_sim._place_x(got.mem, x, plan, tile, x_rows)
        _oracle_place(want, w, x, plan, tile, x_rows)
        np.testing.assert_array_equal(got.mem, want.mem)
    assert got.mem.dtype == np.uint8


def test_x_placement_checks_range():
    plan, x_rows = _plan("broadcast", 4, 4)
    x = np.zeros((2, plan.k), dtype=np.int64)
    x[1, plan.k - 1] = 16
    mem = np.zeros((2, plan.n_blocks, 128, N_COLS), dtype=np.uint8)
    comefa_sim._place_x(mem, x, plan, plan.tiles()[0], x_rows)
    with pytest.raises(AssertionError):
        comefa_sim._place_x(mem, x, plan, plan.tiles()[-1], x_rows)


@pytest.mark.parametrize("g,n_blocks,acc_bits,n", [
    (1, 1, 20, 160), (3, 2, 24, 170), (4, 3, 32, 330), (4, 6, 22, 960)])
def test_extract_batched_matches_per_slot_extract(g, n_blocks, acc_bits, n):
    rng = np.random.default_rng(n + acc_bits)
    grid = ComefaGrid(g, n_blocks=n_blocks)
    grid.mem = rng.integers(0, 2, size=(g, n_blocks, 128, N_COLS),
                            dtype=np.uint8)
    base = 60
    want = np.stack([layout.extract(grid.slot(s), base, acc_bits)
                     .reshape(-1)[:n] for s in range(g)])
    got = comefa_sim._extract_batched(grid, base, acc_bits, n)
    assert got.dtype == np.int64 and got.shape == (g, n)
    np.testing.assert_array_equal(got, want)
