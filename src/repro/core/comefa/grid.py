"""Batched multi-array simulation: a fleet of CoMeFa arrays as ONE dispatch.

The paper's system-level speedups come from driving *many* CoMeFa RAMs in
parallel from shared instruction-generation FSMs (Sec. III-D): every RAM
executes the same instruction each cycle on its own data.  `ComefaArray`
already models that SIMD broadcast across the blocks of one array;
`ComefaGrid` lifts it one level up, to a *grid* of G independent arrays:

  * state is stacked - ``mem[G, n_blocks, 128, 160]`` plus carry/mask
    ``[G, n_blocks, 160]`` - instead of G separate python objects;
  * one shared program executes across all G slots in a single fused
    ``lax.scan`` dispatch over the stacked state (`block._step` is
    rank-polymorphic, so the grid axis is one more elementwise dimension
    - measured ~3x faster than the equivalent ``jax.vmap`` formulation,
    whose batched gather/scatter rules lose to the flat kernel on CPU);
    a fleet-scale sweep costs one trace + one device call rather than G
    python-loop dispatches;
  * programs go through the same keyed encode cache as `ComefaArray`
    (`block.encoded`), so sweeps re-running structurally equal programs
    never re-encode;
  * optionally the grid axis is sharded across devices through
    `parallel/sharding.py`'s logical-rules machinery (the ``"grid"``
    logical axis), turning the same dispatch into a multi-device sweep.

Semantics contract (pinned by `tests/test_grid.py`'s property suite):
slot g of ``ComefaGrid.run(p)`` is bit-identical - mem, carry, mask, and
cycle counts - to an independent ``ComefaArray.run(p)`` on the same
initial state, including ``chain=True`` corner-PE threading and
``run_programs`` latch-reset boundaries.  The grid never chains *across*
slots: slots are independent arrays, each with its own (optionally
chained) block row.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import trace as obs_trace
from . import block, isa, verify
from .block import (ComefaArray, encoded, read_port_word, write_port_word)
from .isa import N_COLS, N_ROWS, ROW_ONES


# One fused dispatch for the whole grid: every engine's step is
# rank-polymorphic over leading state axes, so the grid runs the SAME
# jitted scan as a single array, just with stacked ``[G, nb, R, C]``
# state - every slot executes the shared program in lockstep (the
# Sec. III-D FSM broadcast), the grid axis is one more elementwise
# dimension to XLA (no vmap batching rules), and chain=True shift seams
# stay inside each slot by construction.  Per-slot program dispatch
# (`run_per_slot`) instead vmaps the grid axis - instruction fields
# differ across slots, so it is no longer elementwise; the batched
# gather/scatter rules make it slower than the fused shared path - the
# price of per-slot digit streams, paid in simulator wall-clock while
# the modelled hardware *saves* cycles (zero-skipping returns).
_run_grid = block._run
_run_slotwise = block._run_slotwise


# per-slot program matrices are padded up to a multiple of this quantum so
# the number of distinct scan lengths (= jit retraces) stays bounded across
# a sweep of value-dependent program lengths
_SLOT_PAD_QUANTUM = 32


class _Slot:
    """Per-slot view of grid state, duck-typed like a `ComefaArray`.

    `layout.place` / `layout.extract` / `ChainPlan` only touch ``.mem``
    and ``.n_blocks``, so a numpy view over one grid slot lets every
    existing placement helper address the grid slot-by-slot; hybrid-mode
    port words account their traffic to the owning grid.
    """

    def __init__(self, grid: "ComefaGrid", g: int):
        self._grid = grid
        self.index = g
        self.n_blocks = grid.n_blocks
        self.chain = grid.chain

    @property
    def mem(self) -> np.ndarray:
        return self._grid.mem[self.index]

    @property
    def carry(self) -> np.ndarray:
        return self._grid.carry[self.index]

    @property
    def mask(self) -> np.ndarray:
        return self._grid.mask[self.index]

    def write_word(self, blk: int, addr: int, word: int) -> None:
        write_port_word(self.mem, blk, addr, word)
        self._grid.io_words += 1

    def read_word(self, blk: int, addr: int) -> int:
        word = read_port_word(self.mem, blk, addr)
        self._grid.io_words += 1  # a rejected address counts no traffic
        return word


class ComefaGrid:
    """G independent CoMeFa arrays executing one shared program per dispatch.

    Models a fleet of arrays whose instruction FSMs broadcast the same
    stream (the paper's array-of-arrays evaluation scale): state is G
    stacked `ComefaArray` states, and `run`/`run_programs` execute across
    every slot in a single fused scan dispatch.  Pass a `jax.sharding.Mesh` to
    shard the grid axis across devices (rules come from
    `parallel.sharding`; a grid that doesn't divide the device count
    degrades to replication via the same pruning the model layers use).
    """

    def __init__(self, g: int, n_blocks: int = 1, chain: bool = False,
                 mesh=None, rules=None, engine=None):
        assert g >= 1
        self.g = g
        self.n_blocks = n_blocks
        self.chain = chain
        self.engine = block.get_engine(engine)
        self.cycles = 0           # per-slot compute cycles (slots run in lockstep)
        self.io_words = 0         # port words moved across ALL slots
        self._shardings = (None if mesh is None
                           else grid_shardings(mesh, g, n_blocks, rules))
        self.reset()

    # -- state ------------------------------------------------------------
    def reset(self) -> None:
        mem = np.zeros((self.g, self.n_blocks, N_ROWS, N_COLS),
                       dtype=np.uint8)
        mem[:, :, ROW_ONES, :] = 1
        self._mem = mem
        self._carry = np.zeros((self.g, self.n_blocks, N_COLS),
                               dtype=np.uint8)
        self._mask = np.zeros((self.g, self.n_blocks, N_COLS),
                              dtype=np.uint8)
        self._dev = None          # engine-format device state, when ahead
        self.cycles = 0
        self.io_words = 0
        self.host_syncs = 0       # device->host state materializations
        self.device_puts = 0      # host->device state uploads

    # same lazy host/device state contract as `ComefaArray`: device
    # buffers chain between dispatches; any host access materializes
    # writable numpy (dropping the device copy, since callers mutate the
    # result in place via slot views / placements)
    def _sync_host(self) -> None:
        if self._dev is not None:
            engine = self._active_engine()
            with obs_trace.span("grid.host_sync", engine=engine.name,
                                slots=self.g):
                if obs_trace.enabled():
                    # the wait for the device, apart from the copy and
                    # unpack that follow (to_host would wait anyway)
                    with obs_trace.span("grid.wait"):
                        jax.block_until_ready(self._dev)
                self._mem, self._carry, self._mask = engine.to_host(
                    self._dev)
            block.count_transfer(self._dev, "grid", "d2h", "state")
            self._dev = None
            self.host_syncs += 1
            block._HOST_SYNCS.inc(kind="grid")

    @property
    def mem(self) -> np.ndarray:
        self._sync_host()
        return self._mem

    @mem.setter
    def mem(self, value):
        self._sync_host()         # keep carry/mask coherent before replacing
        self._mem = np.asarray(value)

    @property
    def carry(self) -> np.ndarray:
        self._sync_host()
        return self._carry

    @carry.setter
    def carry(self, value):
        self._sync_host()
        self._carry = np.asarray(value)

    @property
    def mask(self) -> np.ndarray:
        self._sync_host()
        return self._mask

    @mask.setter
    def mask(self, value):
        self._sync_host()
        self._mask = np.asarray(value)

    def slot(self, g: int) -> _Slot:
        """Array-like view of slot g (usable with `layout` helpers)."""
        assert 0 <= g < self.g
        return _Slot(self, g)

    def slots(self) -> List[_Slot]:
        return [self.slot(g) for g in range(self.g)]

    @classmethod
    def from_arrays(cls, arrays: Sequence[ComefaArray],
                    mesh=None, rules=None) -> "ComefaGrid":
        """Stack G equal-shape arrays (state is copied) into one grid.

        Accounting carries over where it is well-defined: `io_words`
        sums across the sources, and `cycles` is inherited when every
        source agrees (the lockstep invariant) - arrays with divergent
        histories restart the grid's lockstep count at 0.
        """
        assert arrays
        nb = arrays[0].n_blocks
        chain = arrays[0].chain
        assert all(a.n_blocks == nb and a.chain == chain for a in arrays), \
            "grid slots must agree on n_blocks and chain"
        grid = cls(len(arrays), n_blocks=nb, chain=chain, mesh=mesh,
                   rules=rules, engine=arrays[0].engine)
        for g, a in enumerate(arrays):
            grid.mem[g] = a.mem
            grid.carry[g] = a.carry
            grid.mask[g] = a.mask
        if len({a.cycles for a in arrays}) == 1:
            grid.cycles = arrays[0].cycles
        grid.io_words = sum(a.io_words for a in arrays)
        return grid

    def to_arrays(self) -> List[ComefaArray]:
        """Split back into G independent arrays (state is copied).

        Each array inherits the grid's lockstep `cycles`; `io_words`
        was accounted grid-wide and cannot be attributed per slot, so
        the split arrays restart it at 0.
        """
        out = []
        for g in range(self.g):
            a = ComefaArray(n_blocks=self.n_blocks, chain=self.chain,
                            engine=self.engine)
            a.mem = self.mem[g].copy()
            a.carry = self.carry[g].copy()
            a.mask = self.mask[g].copy()
            a.cycles = self.cycles
            out.append(a)
        return out

    # -- execution ---------------------------------------------------------
    def run(self, program) -> int:
        """Execute one shared program on every slot.  Returns the per-slot
        processing cycles (identical across slots - one FSM, one stream).
        """
        with obs_trace.span("grid.run", program=block._prog_label(program),
                            slots=self.g) as sp:
            cycles = self._dispatch(encoded(program))
            sp.set(cycles=cycles)
        return cycles

    def run_programs(self, programs, reset_latches: bool = True) -> List[int]:
        """Back-to-back programs in ONE fused dispatch, across all slots.

        Same contract as `ComefaArray.run_programs`: with `reset_latches`
        a one-cycle `isa.latch_clear` is inserted at every boundary
        (charged to the following program), so no program's carry/mask
        latches leak into the next.  Returns per-program cycle counts.
        """
        programs = list(programs)
        with obs_trace.span("grid.run_programs", n=len(programs),
                            slots=self.g) as sp:
            verify.maybe_verify_batch(programs, reset_latches)
            mats = [encoded(p) for p in programs]
            if not mats:
                return []
            mat, counts = block._concat_encoded(mats, reset_latches)
            sp.set(cycles=self._dispatch(mat))
        return counts

    def run_per_slot(self, programs: Sequence) -> List[int]:
        """Execute a DIFFERENT program on every slot, in one dispatch.

        `programs[g]` runs on slot g - the per-slice-FSM configuration:
        each slice of the fleet streams its own operand digits (the
        per-slot stream specialization of `ir.specialize_streams`),
        instead of every slice executing one broadcast stream.  Shorter
        programs pad with no-op cycles (all control fields idle) up to
        the longest slot, so slots stay independent and bit-identical to
        isolated `ComefaArray.run` calls; padding is simulator bookkeeping
        only - `cycles` advances by the *longest real* program (the
        dispatch makespan: slices run concurrently, the slowest bounds
        the wall-clock) and the returned list gives every slot's own
        cycle count.
        """
        assert len(programs) == self.g, (len(programs), self.g)
        with obs_trace.span("grid.run_per_slot", slots=self.g) as sp:
            with obs_trace.span("grid.stack"):
                mats = [encoded(p) for p in programs]
                counts = [int(m.shape[0]) for m in mats]
                longest = max(counts, default=0)
                if longest == 0:
                    return counts
                # bucketed padding bounds the number of distinct scan
                # lengths a sweep of value-dependent programs can trigger
                # (each length is one jit trace)
                t_pad = -(-longest // _SLOT_PAD_QUANTUM) * _SLOT_PAD_QUANTUM
                stack = np.zeros((self.g, t_pad, isa.N_ENGINE_FIELDS),
                                 dtype=np.int32)   # zero fields == idle
                for g, m in enumerate(mats):
                    stack[g, :m.shape[0]] = m
            engine = self._active_engine()
            # makespan = the longest real program: slices run concurrently,
            # the slowest bounds the dispatch
            sp.set(engine=engine.name, makespan=longest,
                   min_slot_cycles=min(counts), padded_to=t_pad)
            self._ensure_device(engine)
            self._dev = engine.run_per_slot(
                self._dev, self._device_prog(stack), self.chain)
            self.cycles += longest
            block._DISPATCHES.inc(kind="grid", engine=engine.name)
            block._DISPATCH_CYCLES.inc(longest, kind="grid",
                                       engine=engine.name)
        return counts

    def _active_engine(self):
        """The engine this dispatch actually uses.

        A sharded grid swaps to the engine's `sharded_fallback` when it
        declares one (a pallas_call does not partition across a mesh;
        the packed-XLA scan shares its state layout, so the swap is free).
        """
        engine = self.engine
        if self._shardings is not None:
            engine = getattr(engine, "sharded_fallback", engine)
        return engine

    def _ensure_device(self, engine) -> None:
        if self._dev is not None:
            return
        with obs_trace.span("grid.upload"):
            dev = engine.to_device(self._mem, self._carry, self._mask)
            if self._shardings is not None:
                # packed state keeps the grid axis leading and the same
                # rank (row axis at -2, lanes packed in place), so the
                # reference specs transfer unchanged
                s_mem, s_latch, _ = self._shardings
                dev = (jax.device_put(dev[0], s_mem),
                       jax.device_put(dev[1], s_latch),
                       jax.device_put(dev[2], s_latch))
        self._dev = dev
        self.device_puts += 1
        block._DEVICE_PUTS.inc(kind="grid")
        block.count_transfer(dev, "grid", "h2d", "state")

    def _device_prog(self, prog: np.ndarray):
        """Program matrix as a device array (sharded when a mesh is set).

        The program sharding spec is fully-replicated (rank-agnostic), so
        the same marshalling serves the shared [T, F] matrix and the
        per-slot [G, T, F] stack; unsharded dispatches go through the
        keyed device-mat cache (frozen encode-cache matrices skip the
        upload entirely).
        """
        with obs_trace.span("grid.program_upload"):
            if self._shardings is not None:
                block.count_transfer((prog,), "grid", "h2d", "program")
                return jax.device_put(jnp.asarray(prog), self._shardings[2])
            return block.device_mat(prog, kind="grid")

    def _dispatch(self, mat: np.ndarray) -> int:
        if mat.shape[0] == 0:
            return 0
        engine = self._active_engine()
        with obs_trace.span("grid.dispatch", engine=engine.name,
                            slots=self.g, cycles=int(mat.shape[0])):
            self._ensure_device(engine)
            self._dev = engine.run(self._dev, self._device_prog(mat),
                                   self.chain)
        self.cycles += int(mat.shape[0])
        block._DISPATCHES.inc(kind="grid", engine=engine.name)
        block._DISPATCH_CYCLES.inc(int(mat.shape[0]), kind="grid",
                                   engine=engine.name)
        return int(mat.shape[0])

    def __repr__(self):
        return (f"ComefaGrid({self.g} slots x {self.n_blocks} blocks, "
                f"chain={self.chain}, {self.cycles} cycles)")


# ---------------------------------------------------------------------------
# sharding the grid axis (parallel/sharding.py rule machinery)
# ---------------------------------------------------------------------------

def grid_mesh(devices=None) -> "jax.sharding.Mesh":
    """A 1-D mesh over the available devices for grid-axis sharding."""
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), ("data",))


def grid_shardings(mesh, g: int, n_blocks: int, rules=None) -> Tuple:
    """(mem, latch, program) NamedShardings for stacked grid state.

    The grid axis carries the logical name ``"grid"`` and resolves
    through the same rules table the model layers use
    (`parallel.sharding.spec_for`, restricted to this mesh's axes); all
    other dims replicate, and the program matrix is fully replicated
    (every device's FSM broadcasts the same stream).  Dimension-aware
    pruning (`shardings_pruned`) degrades a grid that doesn't divide
    the device count to replication, like every other ragged axis in
    the codebase.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ...parallel import sharding as shd
    grid_part = tuple(shd.spec_for(("grid",), rules,
                                   mesh_axes=mesh.axis_names))
    specs = [P(*(grid_part + (None,) * 3)), P(*(grid_part + (None,) * 2))]
    structs = [
        jax.ShapeDtypeStruct((g, n_blocks, N_ROWS, N_COLS), jnp.uint8),
        jax.ShapeDtypeStruct((g, n_blocks, N_COLS), jnp.uint8),
    ]
    mem_sharding, latch_sharding = shd.shardings_pruned(mesh, specs, structs)
    return (mem_sharding, latch_sharding, NamedSharding(mesh, P()))
