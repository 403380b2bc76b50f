"""Bit-packed execution engines for the CoMeFa simulator step.

The reference engine (`block._step`) stores every one-bit cell as its own
uint8 lane: ``mem[..., 128, 160]``.  XLA therefore moves and computes 8x
more bytes than the state holds (and 32x more than machine words would).
The PE datapath, however, is pure bitwise logic - TR mux, XOR, CGEN,
predication - which packs perfectly into machine words, the same
bit-parallel trick in-SRAM computing uses to get word-level throughput out
of single-bit cells (X-SRAM; Bit-Parallel 6T SRAM, PAPERS.md).

This module keeps the same state *semantics* in 1/8 the bytes (1/32 the
lanes):

  * ``mem[..., nb, 128, 160]`` uint8  ->  ``mem[..., nb, 128, 5]`` uint32
    (lane ``c`` lives in word ``c // 32``, bit ``c % 32``, LSB first);
    carry/mask ``[..., nb, 160]``     ->  ``[..., nb, 5]`` uint32;
  * the whole PE datapath is word-parallel bitwise ops: the TR mux is a
    per-truth-table-bit expansion over the four minterm word masks
    (``~a&~b``, ``~a&b``, ``a&~b``, ``a&b``), CGEN/X are and/or/xor on
    packed words, predication and the write enables are bitwise selects,
    and the W1_RIGHT / W2_LEFT shift network (including ``chain=True``
    cross-block threading) becomes funnel shifts with cross-word /
    cross-block boundary words;
  * every instruction-dependent word mask is precomputed *outside* the
    scan (`prepare_fields` vectorizes over the whole program matrix), so
    the per-cycle step is nothing but and/or/xor/shift on packed words
    plus two dynamic row updates;
  * packing/unpacking happens only at the host boundary
    (`ComefaArray`/`ComefaGrid` sync state lazily); the scan itself never
    touches unpacked bits.

Two runners share the datapath:

  * the pure-XLA packed scan (`_run_packed` / `_run_slotwise_packed`) -
    the fallback that works on any backend;
  * the Pallas kernel in `repro.kernels.comefa_step` (`pl.pallas_call`
    over the slot grid, the instruction loop carried in VMEM state,
    interpret-mode on CPU like the other kernels in that package).

Engine selection lives in `block.get_engine` (``ComefaArray(engine=...)``
/ ``REPRO_COMEFA_ENGINE``); the uint8 scan stays the reference engine and
`tests/test_engines.py` pins every packed path bit-identical to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...obs import metrics as obs_metrics
from . import isa

# Pallas kernel calls by mode: "compiled" on a TPU, "interpret" elsewhere
_PALLAS_CALLS = obs_metrics.counter("comefa.pallas_calls")

# field indices in the encoded program matrix (same layout as block._F)
_F = {name: i for i, name in enumerate(isa.ENGINE_FIELD_NAMES)}

PACK = 32                        # lanes per packed word
N_WORDS = isa.N_COLS // PACK     # 5 uint32 words per 160-lane row
assert isa.N_COLS % PACK == 0

_ALL = np.uint32(0xFFFFFFFF)
_SHIFTS = np.arange(PACK, dtype=np.uint32)


# ---------------------------------------------------------------------------
# host-boundary pack / unpack (numpy: runs once per host<->device sync)
# ---------------------------------------------------------------------------

def pack_bits(bits: np.ndarray) -> np.ndarray:
    """uint8 {0,1} ``[..., C]`` (C % 32 == 0) -> uint32 ``[..., C // 32]``.

    Lane ``c`` -> word ``c // 32``, bit ``c % 32`` (LSB first) - the one
    layout every engine and the Pallas kernel agree on.
    """
    bits = np.asarray(bits)
    assert bits.shape[-1] % PACK == 0, bits.shape
    b = bits.astype(np.uint32).reshape(bits.shape[:-1] + (-1, PACK))
    # disjoint bit positions: the sum IS the bitwise OR, and fits uint32
    return (b << _SHIFTS).sum(axis=-1, dtype=np.uint64).astype(np.uint32)


def unpack_bits(words: np.ndarray) -> np.ndarray:
    """Inverse of `pack_bits`: uint32 ``[..., W]`` -> uint8 ``[..., W*32]``."""
    words = np.asarray(words, dtype=np.uint32)
    bits = ((words[..., None] >> _SHIFTS) & np.uint32(1)).astype(np.uint8)
    return bits.reshape(words.shape[:-1] + (-1,))


# ---------------------------------------------------------------------------
# the word-parallel PE datapath (shared by the XLA scan and the Pallas
# kernel - only the row read/write plumbing differs between them)
# ---------------------------------------------------------------------------

def prepare_fields(get):
    """Engine fields -> the packed datapath's operand bundle.

    ``get(name)`` returns the raw int field value - a ``[T]`` column when
    preparing a whole program matrix ahead of the XLA scan (every leaf
    then rides the scan as an ``xs`` slice), or a traced scalar when the
    Pallas kernel prepares one instruction inside its on-chip loop.  All
    multi-way selects collapse here into per-option all-ones/all-zeros
    word masks, so the per-cycle datapath is pure and/or/xor/shift.
    """
    def flag(name):
        return jnp.where(get(name) == 1, jnp.uint32(_ALL), jnp.uint32(0))

    def sel(name, val):
        return jnp.where(get(name) == val, jnp.uint32(_ALL), jnp.uint32(0))

    tt = get("truth_table")
    b_ext = flag("b_ext")
    wp1, wp2 = flag("wp1_en"), flag("wp2_en")
    ce, me = flag("c_en"), flag("m_en")
    return dict(
        src1=get("src1_row"), src2=get("src2_row"),
        dst=get("dst_row"), dst2=get("dst2_row"),
        # TR truth-table bits as minterm masks: tt[i] selects (A<<1)|B == i
        tt0=jnp.where((tt >> 0) & 1 == 1, jnp.uint32(_ALL), jnp.uint32(0)),
        tt1=jnp.where((tt >> 1) & 1 == 1, jnp.uint32(_ALL), jnp.uint32(0)),
        tt2=jnp.where((tt >> 2) & 1 == 1, jnp.uint32(_ALL), jnp.uint32(0)),
        tt3=jnp.where((tt >> 3) & 1 == 1, jnp.uint32(_ALL), jnp.uint32(0)),
        # operand-B substitution (OOOR): b = (b_read & keep_b) | ext_and
        keep_b=~b_ext, ext_and=flag("ext_bit") & b_ext,
        # latch control
        crst_keep=~flag("c_rst"), ce=ce, nce=~ce, me=me, nme=~me,
        # per-port write enables, wp folded in:
        # we = pa | (mask & pm) | (carry & pc) | (~carry & pn)
        p1a=sel("pred_sel", isa.PRED_ALWAYS) & wp1,
        p1m=sel("pred_sel", isa.PRED_MASK) & wp1,
        p1c=sel("pred_sel", isa.PRED_CARRY) & wp1,
        p1n=sel("pred_sel", isa.PRED_NOT_CARRY) & wp1,
        p2a=sel("pred2_sel", isa.PRED_ALWAYS) & wp2,
        p2m=sel("pred2_sel", isa.PRED_MASK) & wp2,
        p2c=sel("pred2_sel", isa.PRED_CARRY) & wp2,
        p2n=sel("pred2_sel", isa.PRED_NOT_CARRY) & wp2,
        # write-mux one-hots (W1_DIN / W2_DIN / W2_ZERO all drive 0)
        v1s=sel("w1_sel", isa.W1_S), v1r=sel("w1_sel", isa.W1_RIGHT),
        v2c=sel("w2_sel", isa.W2_CARRY), v2l=sel("w2_sel", isa.W2_LEFT),
    )


def prepare_program(prog):
    """Whole encoded ``[T, F]`` matrix -> scan-ready field bundle."""
    return prepare_fields(lambda name: prog[:, _F[name]])


def datapath(a, b_read, carry, mask, x, chain: bool):
    """One PE cycle on packed words; returns the write-back bundle.

    ``a`` / ``b_read`` are the packed Port-A/Port-B row reads
    (``[..., nb, W]`` uint32), ``carry`` / ``mask`` the packed latches,
    ``x`` one instruction's `prepare_fields` bundle.  Returns
    ``(carry_next, mask_next, val1, we1, val2, we2)`` - the caller owns
    the two read-modify-write row updates (their order, port 1 then
    port 2, matters when both target the same row).
    """
    b = (b_read & x["keep_b"]) | x["ext_and"]

    # ---- compute: TR mux as the 4-minterm word expansion ----------------
    na, nb_ = ~a, ~b
    ab = a & b
    tr = ((x["tt0"] & na & nb_) | (x["tt1"] & na & b)
          | (x["tt2"] & a & nb_) | (x["tt3"] & ab))
    c_in = carry & x["crst_keep"]                       # gated carry input
    s = tr ^ c_in                                       # gate X
    cgen = ab | (c_in & (a ^ b))                        # CGEN
    carry_next = (cgen & x["ce"]) | (carry & x["nce"])
    mask_next = (tr & x["me"]) | (mask & x["nme"])

    # ---- predicated write enables on the *latched* values ---------------
    ncarry = ~carry
    we1 = (x["p1a"] | (mask & x["p1m"]) | (carry & x["p1c"])
           | (ncarry & x["p1n"]))
    we2 = (x["p2a"] | (mask & x["p2m"]) | (carry & x["p2c"])
           | (ncarry & x["p2n"]))

    # ---- shift network: funnel shifts with boundary words ---------------
    # lane c+1 -> lane c (from_right) crosses words via word w+1's bit 0;
    # lane c-1 -> lane c (from_left) via word w-1's bit 31.  chain=True
    # threads corner PEs: block k's high boundary word is block k+1's
    # word 0 (bit 0 used), its low boundary block k-1's word W-1 (bit 31).
    # A single block has no neighbour (and Mosaic refuses the empty slice).
    if chain and s.shape[-2] > 1:
        hi = jnp.concatenate(
            [s[..., 1:, :1], jnp.zeros_like(s[..., :1, :1])], axis=-2)
        lo = jnp.concatenate(
            [jnp.zeros_like(s[..., :1, -1:]), s[..., :-1, -1:]], axis=-2)
    else:
        hi = jnp.zeros_like(s[..., :1])
        lo = hi
    s_hi = jnp.concatenate([s[..., 1:], hi], axis=-1)   # word w+1
    s_lo = jnp.concatenate([lo, s[..., :-1]], axis=-1)  # word w-1
    from_right = (s >> 1) | (s_hi << (PACK - 1))
    from_left = (s << 1) | (s_lo >> (PACK - 1))

    # W2 carry source is the raw latch (pre-update)
    val1 = (s & x["v1s"]) | (from_right & x["v1r"])
    val2 = (carry & x["v2c"]) | (from_left & x["v2l"])
    return carry_next, mask_next, val1, we1, val2, we2


def _step_packed(chain: bool, state, x):
    """One CoMeFa cycle on packed state - `block._step` in 1/8 the bytes.

    ``state = (mem[..., nb, R, W], carry[..., nb, W], mask[..., nb, W])``
    uint32, rank-polymorphic over leading axes exactly like the reference
    step (the grid stacks a leading G axis and reuses this scan).  ``x``
    is one instruction's slice of the `prepare_program` bundle.
    """
    mem, carry, mask = state
    row_axis = mem.ndim - 2

    def row(i):
        return lax.dynamic_index_in_dim(mem, i, axis=row_axis,
                                        keepdims=False)

    a = row(x["src1"])
    b_read = row(x["src2"])
    carry_next, mask_next, val1, we1, val2, we2 = datapath(
        a, b_read, carry, mask, x, chain)

    # port 1 writes first; port 2 reads the updated row (matters when a
    # co-issued pair degenerates to dst2 == dst - same order as reference)
    old1 = row(x["dst"])
    mem = lax.dynamic_update_index_in_dim(
        mem, (old1 & ~we1) | (val1 & we1), x["dst"], axis=row_axis)
    old2 = lax.dynamic_index_in_dim(mem, x["dst2"], axis=row_axis,
                                    keepdims=False)
    mem = lax.dynamic_update_index_in_dim(
        mem, (old2 & ~we2) | (val2 & we2), x["dst2"], axis=row_axis)
    return (mem, carry_next, mask_next), None


@functools.partial(jax.jit, static_argnames=("chain",))
def _run_packed(mem, carry, mask, prog, chain: bool):
    (mem, carry, mask), _ = lax.scan(
        functools.partial(_step_packed, chain), (mem, carry, mask),
        prepare_program(prog))
    return mem, carry, mask


@functools.partial(jax.jit, static_argnames=("chain",))
def _run_slotwise_packed(mem, carry, mask, progs, chain: bool):
    """Per-slot program dispatch on packed state (grid `run_per_slot`)."""
    def one(m, c, k, p):
        (m, c, k), _ = lax.scan(
            functools.partial(_step_packed, chain), (m, c, k),
            prepare_program(p))
        return m, c, k

    return jax.vmap(one)(mem, carry, mask, progs)


# ---------------------------------------------------------------------------
# engine objects (the strategy `ComefaArray`/`ComefaGrid` dispatch through)
# ---------------------------------------------------------------------------

class PackedXlaEngine:
    """Packed uint32 state, pure-XLA scan - works on every backend."""

    name = "packed"

    def to_device(self, mem, carry, mask):
        return (jnp.asarray(pack_bits(mem)), jnp.asarray(pack_bits(carry)),
                jnp.asarray(pack_bits(mask)))

    def to_host(self, state):
        mem, carry, mask = (np.array(x) for x in state)
        return unpack_bits(mem), unpack_bits(carry), unpack_bits(mask)

    def pack_rows(self, bits):
        """Device bit rows ``[..., C]`` of 0/1 -> packed uint32
        ``[..., C // 32]``: `pack_bits` in jnp, traceable."""
        b = bits.astype(jnp.uint32).reshape(bits.shape[:-1] + (-1, PACK))
        return jnp.sum(b << jnp.asarray(_SHIFTS), axis=-1, dtype=jnp.uint32)

    def unpack_rows(self, rows):
        """Packed rows ``[..., W]`` -> uint8 bits ``[..., W * 32]``:
        `unpack_bits` in jnp, traceable."""
        bits = (rows[..., None] >> jnp.asarray(_SHIFTS)) & jnp.uint32(1)
        return bits.astype(jnp.uint8).reshape(rows.shape[:-1] + (-1,))

    def run(self, state, prog, chain: bool):
        return _run_packed(*state, prog, chain)

    def run_per_slot(self, state, progs, chain: bool):
        return _run_slotwise_packed(*state, progs, chain)


class PallasEngine(PackedXlaEngine):
    """Packed state driven by the Pallas step kernel.

    Same packed layout (so `to_device`/`to_host` are inherited); the scan
    runs inside one `pl.pallas_call` over the slot grid
    (`repro.kernels.comefa_step`): compiled by Mosaic on a TPU, in the
    Pallas interpreter on every other backend.  Each call counts under
    ``comefa.pallas_calls{mode=compiled|interpret}``.  Sharded grid
    dispatches fall back to the XLA scan (`sharded_fallback`): a
    pallas_call does not partition across a mesh.
    """

    name = "pallas"

    def __init__(self):
        self.sharded_fallback = PackedXlaEngine()
        self.interpret = jax.default_backend() != "tpu"

    def _run(self, mem, carry, mask, prog, chain: bool, per_slot: bool):
        from ...kernels import comefa_step    # deferred: optional dep gate
        _PALLAS_CALLS.inc(mode="interpret" if self.interpret else "compiled")
        return comefa_step.run_packed(mem, carry, mask, prog, chain=chain,
                                      per_slot=per_slot,
                                      interpret=self.interpret)

    def run(self, state, prog, chain: bool):
        mem, carry, mask = state
        if mem.ndim == 3:      # single array: add the slot axis the grid has
            out = self._run(mem[None], carry[None], mask[None], prog,
                            chain, per_slot=False)
            return tuple(x[0] for x in out)
        return self._run(mem, carry, mask, prog, chain, per_slot=False)

    def run_per_slot(self, state, progs, chain: bool):
        return self._run(*state, progs, chain, per_slot=True)


def pallas_available() -> bool:
    """True when the Pallas toolchain imports (it is optional at runtime)."""
    try:
        from ...kernels import comefa_step  # noqa: F401
        return True
    except ImportError:     # pragma: no cover - environment-dependent
        return False


_PACKED = PackedXlaEngine()
_PALLAS = None


def get_engine(name: str):
    """Packed-engine registry half of `block.get_engine`.

    ``"packed"`` auto-selects: the compiled Pallas kernel on a TPU (or an
    error, never a quiet swap to the XLA scan), the pure-XLA packed scan
    elsewhere (Pallas interpret mode emulates - correct but not faster -
    so CPU/GPU default to XLA).  ``"packed-xla"`` and ``"pallas"`` force
    one side.
    """
    global _PALLAS
    if name == "packed":
        name = "pallas" if jax.default_backend() == "tpu" else "packed-xla"
    if name == "packed-xla":
        return _PACKED
    if name == "pallas":
        if not pallas_available():
            raise RuntimeError(
                "engine 'pallas' requested but jax.experimental.pallas "
                "is unavailable; use engine='packed-xla'")
        if _PALLAS is None:
            _PALLAS = PallasEngine()
        return _PALLAS
    raise ValueError(f"unknown CoMeFa engine {name!r} "
                     "(expected reference|packed|packed-xla|pallas)")
