"""Pallas kernel for the bit-packed CoMeFa simulator step.

The packed engine (`core.comefa.engine_packed`) carries the whole PE
datapath as word-parallel bitwise ops on uint32 words.  This module runs
that datapath inside ONE `pl.pallas_call`: the grid iterates over slots
(grid slots for `ComefaGrid`, a single slot for `ComefaArray`), each
kernel instance owns its slot's packed state ``[nb, 128, 5]`` in VMEM,
and the instruction stream is a `fori_loop` carried entirely on-chip -
the row reads, the PE logic, and the write-backs never leave VMEM, and
the carry/mask latches ride the loop as register values.

Two program layouts serve the two grid dispatch modes:

  * ``per_slot=False``: one shared ``[T, F]`` program, every slot's block
    spec maps to the same matrix (the Sec. III-D broadcast FSM);
  * ``per_slot=True``: a stacked ``[S, T, F]`` program, slot s scans its
    own stream (`ComefaGrid.run_per_slot`'s per-slice FSM).

`engine_packed.PallasEngine` runs it Mosaic-compiled on a TPU and in
interpret mode elsewhere - bit-identical, if not faster, than the
pure-XLA packed scan it mirrors (`tests/test_engines.py` pins both;
`tests/test_tpu_compile.py` compiles it for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.comefa import isa
from ..core.comefa.engine_packed import N_WORDS, datapath, prepare_fields

_F = {name: i for i, name in enumerate(isa.ENGINE_FIELD_NAMES)}


def _step_kernel(prog_ref, mem_in, carry_in, mask_in,
                 mem_out, carry_out, mask_out, *, chain: bool, n_instr: int):
    # materialize this slot's state in the output refs, then scan in place
    mem_out[...] = mem_in[...]

    def body(t, latches):
        carry, mask = latches                       # [nb, W] loop registers
        # this cycle's encoded fields ([F] vector), then the shared
        # word-mask bundle; the selects stay on-chip scalars, cheap/step
        fields = prog_ref[pl.ds(0, 1), pl.ds(t, 1), :][0, 0]
        x = prepare_fields(lambda name: fields[_F[name]])

        def row(i):
            # slot axis and row axis as width-1 dynamic slices: interpret
            # mode's discharge rejects bare int indices mixed with pl.ds
            return mem_out[pl.ds(0, 1), :, pl.ds(i, 1), :][0, :, 0, :]

        a = row(x["src1"])
        b_read = row(x["src2"])
        carry_next, mask_next, val1, we1, val2, we2 = datapath(
            a, b_read, carry, mask, x, chain)

        def write(i, val, we):
            idx = (pl.ds(0, 1), slice(None), pl.ds(i, 1), slice(None))
            old = mem_out[idx][0, :, 0, :]
            merged = (old & ~we) | (val & we)
            mem_out[idx] = merged[None, :, None, :]

        # port 1 retires before port 2 reads (same order as the scans)
        write(x["dst"], val1, we1)
        write(x["dst2"], val2, we2)
        return carry_next, mask_next

    carry, mask = jax.lax.fori_loop(
        0, n_instr, body, (carry_in[0], mask_in[0]))
    carry_out[...] = carry[None]
    mask_out[...] = mask[None]


@functools.partial(jax.jit,
                   static_argnames=("chain", "per_slot", "interpret"))
def run_packed(mem, carry, mask, prog, *, chain: bool, per_slot: bool,
               interpret: bool):
    """Execute a packed program matrix with the Pallas step kernel.

    mem ``[S, nb, 128, W]`` uint32, carry/mask ``[S, nb, W]`` uint32;
    prog int32 ``[T, F]`` (shared) or ``[S, T, F]`` (``per_slot=True``).
    Returns the updated ``(mem, carry, mask)``.  ``interpret`` runs the
    Pallas interpreter (any backend) instead of the Mosaic-compiled kernel.
    """
    s, nb, n_rows, w = mem.shape
    assert w == N_WORDS, mem.shape
    prog3 = prog if per_slot else prog[None]
    t, f = prog3.shape[-2:]
    prog_map = ((lambda i: (i, 0, 0)) if per_slot
                else (lambda i: (0, 0, 0)))
    state_specs = [
        pl.BlockSpec((1, nb, n_rows, w), lambda i: (i, 0, 0, 0)),
        pl.BlockSpec((1, nb, w), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, nb, w), lambda i: (i, 0, 0)),
    ]
    return pl.pallas_call(
        functools.partial(_step_kernel, chain=chain, n_instr=t),
        grid=(s,),
        in_specs=[pl.BlockSpec((1, t, f), prog_map)] + state_specs,
        out_specs=list(state_specs),
        out_shape=[jax.ShapeDtypeStruct(mem.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(carry.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(mask.shape, jnp.uint32)],
        interpret=interpret,
    )(prog3, mem, carry, mask)
