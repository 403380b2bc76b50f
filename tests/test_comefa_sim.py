"""Bit-level CoMeFa simulator tests: arithmetic correctness + paper cycle
counts (Secs. III-E, III-F, III-G, III-I of the paper)."""
import numpy as np
import pytest

from repro.core.comefa import (ComefaArray, N_COLS, isa, layout, program,
                               timing)
from repro.kernels import ref

RNG = np.random.default_rng(0)


def fresh(n_blocks=1, chain=False):
    return ComefaArray(n_blocks=n_blocks, chain=chain)


def rand_u(bits, n=N_COLS, rng=RNG):
    return rng.integers(0, 1 << bits, size=n, dtype=np.int64)


def word_rows(words):
    """uint32 words [rows, N_COLS // 32] -> bit rows [rows, N_COLS]: lane j
    of a row holds bit j % 32 of word j // 32."""
    shifts = np.arange(32, dtype=np.uint32)
    bits = (np.asarray(words, np.uint32)[..., None] >> shifts) & 1
    return bits.reshape(len(words), -1).astype(np.uint8)


def row_words(rows):
    """Inverse of `word_rows`: bit rows [rows, 32 * w] -> uint32 [rows, w]."""
    shifts = np.arange(32, dtype=np.uint32)
    bits = np.asarray(rows, np.uint32).reshape(len(rows), -1, 32)
    return (bits << shifts).sum(axis=-1, dtype=np.uint32)


# ---------------------------------------------------------------------------
# ISA encode/decode
# ---------------------------------------------------------------------------

def test_isa_roundtrip():
    rng = np.random.default_rng(1)
    for _ in range(200):
        kw = {}
        for name, _, width in isa.FIELDS:
            kw[name] = int(rng.integers(0, 1 << width))
        ins = isa.Instr(**kw)
        word = ins.encode()
        assert 0 <= word < (1 << isa.WORD_BITS)
        assert isa.Instr.decode(word) == ins


def test_isa_field_ranges():
    with pytest.raises(ValueError):
        isa.Instr(src1_row=128)
    with pytest.raises(ValueError):
        isa.Instr(truth_table=16)


# ---------------------------------------------------------------------------
# fixed point add / sub / mul: exactness + exact paper cycle counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8, 16])
def test_add_exact_and_cycles(n):
    arr = fresh()
    a, b = rand_u(n), rand_u(n)
    layout.place(arr, a, 0, n)
    layout.place(arr, b, n, n)
    prog = program.add(list(range(n)), list(range(n, 2 * n)),
                       list(range(2 * n, 3 * n + 1)))
    cyc = arr.run(prog)
    assert cyc == timing.add_cycles(n) == n + 1
    got = layout.extract(arr, 2 * n, n + 1, block=0)
    np.testing.assert_array_equal(got, a + b)


@pytest.mark.parametrize("n", [4, 8])
def test_sub_exact_and_cycles(n):
    arr = fresh()
    a, b = rand_u(n), rand_u(n)
    layout.place(arr, a, 0, n)
    layout.place(arr, b, n, n)
    dst = list(range(2 * n, 3 * n + 1))
    tmp = list(range(3 * n + 1, 4 * n + 1))
    prog = program.sub(list(range(n)), list(range(n, 2 * n)), dst, tmp)
    cyc = arr.run(prog)
    assert cyc == timing.sub_cycles(n)              # incl. carry-out store
    got = layout.extract(arr, 2 * n, n, block=0)
    np.testing.assert_array_equal(got, (a - b) & ((1 << n) - 1))
    borrow_free = layout.extract(arr, 3 * n, 1, block=0)
    np.testing.assert_array_equal(borrow_free, (a >= b).astype(np.int64))


@pytest.mark.parametrize("n", [2, 3, 4, 8])
def test_mul_exact_and_cycles(n):
    arr = fresh()
    a, b = rand_u(n), rand_u(n)
    layout.place(arr, a, 0, n)
    layout.place(arr, b, n, n)
    dst = list(range(2 * n, 4 * n))
    prog = program.mul(list(range(n)), list(range(n, 2 * n)), dst)
    cyc = arr.run(prog)
    assert cyc == timing.mul_cycles(n) == n * n + 3 * n - 2   # paper formula
    got = layout.extract(arr, 2 * n, 2 * n, block=0)
    np.testing.assert_array_equal(got, a * b)


def test_mul_is_simd_across_blocks():
    arr = fresh(n_blocks=3)
    n = 6
    a = np.stack([rand_u(n) for _ in range(3)])
    b = np.stack([rand_u(n) for _ in range(3)])
    layout.place(arr, a, 0, n)
    layout.place(arr, b, n, n)
    prog = program.mul(list(range(n)), list(range(n, 2 * n)),
                       list(range(2 * n, 4 * n)))
    arr.run(prog)
    got = layout.extract(arr, 2 * n, 2 * n)
    np.testing.assert_array_equal(got, a * b)


# ---------------------------------------------------------------------------
# logic ops, predication, OOOR
# ---------------------------------------------------------------------------

def test_bitwise_logic_ops():
    arr = fresh()
    n = 8
    a, b = rand_u(n), rand_u(n)
    layout.place(arr, a, 0, n)
    layout.place(arr, b, n, n)
    for tt, fn in [(isa.TT_AND, np.bitwise_and), (isa.TT_OR, np.bitwise_or),
                   (isa.TT_XOR, np.bitwise_xor)]:
        arr.run(program.logic2(list(range(n)), list(range(n, 2 * n)),
                               list(range(2 * n, 3 * n)), tt))
        got = layout.extract(arr, 2 * n, n, block=0)
        np.testing.assert_array_equal(got, fn(a, b))


def test_add_ext_constant():
    arr = fresh()
    n = 8
    a = rand_u(n)
    layout.place(arr, a, 0, n)
    const = 0x5A
    bits = [(const >> i) & 1 for i in range(n)]
    prog = program.add_ext(list(range(n)), bits, list(range(n, 2 * n + 1)))
    arr.run(prog)
    got = layout.extract(arr, n, n + 1, block=0)
    np.testing.assert_array_equal(got, a + const)


def test_ooor_dot_skips_zero_bits_and_matches():
    arr = fresh()
    k, wb, xb, accb = 4, 6, 6, 20
    w = np.stack([rand_u(wb) for _ in range(k)])        # [k, lanes]
    x = RNG.integers(0, 1 << xb, size=k)
    w_rows = []
    for j in range(k):
        rows = list(range(j * wb, (j + 1) * wb))
        layout.place(arr, w[j], rows[0], wb)
        w_rows.append(rows)
    acc = list(range(k * wb, k * wb + accb))
    prog = program.ooor_dot(w_rows, list(x), xb, acc)
    cyc = arr.run(prog)
    got = layout.extract(arr, k * wb, accb, block=0)
    expect = (w * x[:, None]).sum(axis=0)
    np.testing.assert_array_equal(got, expect)
    # OOOR: cycles proportional to popcount, not to x_bits
    total_pop = sum(int(bin(v).count("1")) for v in x)
    assert cyc <= accb + total_pop * (accb + 2)
    dense_sched = accb + k * xb * (accb + 2)
    assert cyc < dense_sched                           # beat naive schedule


# ---------------------------------------------------------------------------
# shifts + chaining (Sec. III-F)
# ---------------------------------------------------------------------------

def test_shift_left_within_block():
    arr = fresh()
    n = 5
    a = rand_u(n)
    layout.place(arr, a, 0, n)
    arr.run(program.shift_lanes(list(range(n)), list(range(n, 2 * n)),
                                left=True))
    got = layout.extract(arr, n, n, block=0)
    expect = np.concatenate([a[1:], [0]])               # lane i <- lane i+1
    np.testing.assert_array_equal(got, expect)


def test_shift_right_within_block():
    arr = fresh()
    n = 5
    a = rand_u(n)
    layout.place(arr, a, 0, n)
    arr.run(program.shift_lanes(list(range(n)), list(range(n, 2 * n)),
                                left=False))
    got = layout.extract(arr, n, n, block=0)
    expect = np.concatenate([[0], a[:-1]])
    np.testing.assert_array_equal(got, expect)


def test_chained_shift_crosses_blocks():
    arr = fresh(n_blocks=2, chain=True)
    n = 3
    a = np.stack([rand_u(n), rand_u(n)])
    layout.place(arr, a, 0, n)
    arr.run(program.shift_lanes(list(range(n)), list(range(n, 2 * n)),
                                left=True))
    got = layout.extract(arr, n, n)
    flat = a.reshape(2 * N_COLS // N_COLS, -1).reshape(-1)
    expect = np.concatenate([flat[1:], [0]]).reshape(2, N_COLS)
    np.testing.assert_array_equal(got, expect)


# ---------------------------------------------------------------------------
# reduction (Sec. IV-C)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("steps", [1, 2, 3])
def test_reduce_tree(steps):
    arr = fresh()
    n = 6
    vals = rand_u(n)
    layout.place(arr, vals, 0, n)
    width_rows = list(range(0, n + steps + 1))
    scratch = list(range(n + steps + 1, 2 * (n + steps) + 2))
    prog = program.reduce_tree(width_rows, scratch, n, steps)
    cyc = arr.run(prog)
    assert cyc == timing.reduction_cycles(n, steps=steps)
    got = layout.extract(arr, 0, n + steps, block=0)
    g = 1 << steps
    expect_groups = vals.reshape(-1, g).sum(axis=1)
    np.testing.assert_array_equal(got[::g], expect_groups)


# ---------------------------------------------------------------------------
# database search / RAID (Sec. IV-C)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [16, 8, 12, 20])
def test_search_replace(n):
    arr = fresh()
    recs = rand_u(n)
    key = int(recs[7])                                  # ensure >=1 match
    layout.place(arr, recs, 0, n)
    tmp = list(range(n, 2 * n))
    prog = program.search_replace(list(range(n)), key, n, tmp)
    cyc = arr.run(prog)
    assert cyc == timing.search_cycles(n)
    got = layout.extract(arr, 0, n, block=0)
    np.testing.assert_array_equal(got, ref.search_replace_ref(recs, key))
    # the mask latch is left holding the match flag of every lane
    np.testing.assert_array_equal(arr.mask[0], recs == key)


def test_raid_rebuild():
    arr = fresh()
    n_drives, words = 3, 8
    data = [rand_u(1) for _ in range(n_drives)]         # 1-bit rows = raw rows
    # untransposed: each row is a full 160-bit operand
    rows = []
    for d in range(n_drives):
        arr.mem[0, d, :] = (data[d] & 1).astype(np.uint8)
        rows.append([d])
    parity = np.bitwise_xor.reduce([d & 1 for d in data])
    lost = data[0] & 1
    surviving = [[1], [2]]
    arr.mem[0, 10, :] = parity.astype(np.uint8)
    prog = program.raid_rebuild(surviving, [10], [20])
    arr.run(prog)
    np.testing.assert_array_equal(arr.mem[0, 20, :], lost)


def _raid(stripes, rows_per_stripe):
    """Stripes of uint32 words [s, rows * 5], one 160-bit row per 5 words:
    stripe 0 goes in as the parity operand, the rest as data; returns the
    rebuilt stripe's words."""
    arr = fresh()
    rows = []
    for s, words in enumerate(stripes):
        base = s * rows_per_stripe
        arr.mem[0, base:base + rows_per_stripe, :] = word_rows(
            words.reshape(rows_per_stripe, -1))
        rows.append(list(range(base, base + rows_per_stripe)))
    out = list(range(100, 100 + rows_per_stripe))
    arr.run(program.raid_rebuild(rows[1:], rows[0], out))
    return row_words(arr.mem[0, 100:100 + rows_per_stripe, :]).reshape(-1)


def test_raid_rebuild_matches_xor_ref():
    stripes = RNG.integers(0, 2**32, size=(5, 4 * N_COLS // 32),
                           dtype=np.uint64).astype(np.uint32)
    np.testing.assert_array_equal(_raid(stripes, 4),
                                  ref.raid_xor_ref(stripes))


def test_raid_rebuild_recovers_lost_stripe():
    data = RNG.integers(0, 2**32, size=(4, 3 * N_COLS // 32),
                        dtype=np.uint64).astype(np.uint32)
    parity = ref.raid_xor_ref(data)
    survivors = np.stack([parity, data[0], data[1], data[3]])
    np.testing.assert_array_equal(_raid(survivors, 3), data[2])


# ---------------------------------------------------------------------------
# floating point (Sec. III-G)
# ---------------------------------------------------------------------------

def _fp_fields(v, e_bits, m_bits, rng):
    """Random normalized fp fields (sign, exp, mantissa)."""
    s = rng.integers(0, 2, size=v)
    e = rng.integers(1, (1 << e_bits) - 1, size=v)
    m = rng.integers(0, 1 << m_bits, size=v)
    return s, e, m


def _fp_value(s, e, m, e_bits, m_bits):
    bias = (1 << (e_bits - 1)) - 1
    return (-1.0) ** s * (1 + m / (1 << m_bits)) * 2.0 ** (e - bias)


def _fp_mul_oracle(ea, ma, eb, mb, e_bits, m_bits):
    """Word-level oracle with the same truncation semantics as the program."""
    bias = (1 << (e_bits - 1)) - 1
    A = (1 << m_bits) + ma
    B = (1 << m_bits) + mb
    P = A * B
    top = (P >> (2 * m_bits + 1)) & 1
    m_out = np.where(top == 1,
                     (P >> (m_bits + 1)) & ((1 << m_bits) - 1),
                     (P >> m_bits) & ((1 << m_bits) - 1))
    e_out = (ea + eb - bias + top) & ((1 << e_bits) - 1)
    return e_out, m_out


@pytest.mark.parametrize("e_bits,m_bits", [(4, 3), (5, 10), (6, 9)])
def test_fp_mul_bit_exact_vs_oracle(e_bits, m_bits):
    rng = np.random.default_rng(7)
    arr = fresh()
    E, M = e_bits, m_bits
    sa, ea, ma = _fp_fields(N_COLS, E, M, rng)
    sb, eb, mb = _fp_fields(N_COLS, E, M, rng)
    # keep result exponent in range (no overflow handling in scope)
    bias = (1 << (E - 1)) - 1
    ea = np.clip(ea, bias - 2, bias + 2)
    eb = np.clip(eb, bias - 2, bias + 2)
    r = 0
    def rows(k):
        nonlocal r
        out = list(range(r, r + k)); r += k
        return out
    ra_s, ra_e, ra_m = rows(1), rows(E), rows(M)
    rb_s, rb_e, rb_m = rows(1), rows(E), rows(M)
    ro_s, ro_e, ro_m = rows(1), rows(E), rows(M)
    scratch = rows(E + 3 + 2 * M + 2 * (M + 1))
    layout.place(arr, sa, ra_s[0], 1)
    layout.place(arr, ea, ra_e[0], E)
    layout.place(arr, ma, ra_m[0], M)
    layout.place(arr, sb, rb_s[0], 1)
    layout.place(arr, eb, rb_e[0], E)
    layout.place(arr, mb, rb_m[0], M)
    prog = program.fp_mul(0, ra_e, ra_m, 0, rb_e, rb_m, ra_s[0], rb_s[0],
                          ro_s[0], ro_e, ro_m, scratch, E, M)
    cyc = arr.run(prog)
    # paper formula is approximate - our program is within 2 cycles of it
    paper = timing.fp_mul_cycles(E, M)
    assert abs(cyc - paper) <= 4, (cyc, paper)
    s_got = layout.extract(arr, ro_s[0], 1, block=0)
    e_got = layout.extract(arr, ro_e[0], E, block=0)
    m_got = layout.extract(arr, ro_m[0], M, block=0)
    e_exp, m_exp = _fp_mul_oracle(ea, ma, eb, mb, E, M)
    np.testing.assert_array_equal(s_got, sa ^ sb)
    np.testing.assert_array_equal(e_got, e_exp)
    np.testing.assert_array_equal(m_got, m_exp)


def _fp_add_oracle(ea, ma, eb, mb, e_bits, m_bits):
    """Same-sign magnitude add with truncating alignment."""
    big_is_a = ea >= eb
    e_big = np.where(big_is_a, ea, eb)
    m_big = (1 << m_bits) + np.where(big_is_a, ma, mb)
    m_small = (1 << m_bits) + np.where(big_is_a, mb, ma)
    d = np.abs(ea.astype(np.int64) - eb.astype(np.int64))
    d_clip = np.minimum(d, m_bits + 1)
    m_small_aligned = m_small >> d_clip
    # barrel shifter width: shifts >= 2^e_bits wrap physically; our inputs
    # keep d small so this matches
    ssum = m_big + m_small_aligned
    top = (ssum >> (m_bits + 1)) & 1
    m_out = np.where(top == 1, (ssum >> 1) & ((1 << m_bits) - 1),
                     ssum & ((1 << m_bits) - 1))
    e_out = e_big + top
    return e_out, m_out


@pytest.mark.parametrize("e_bits,m_bits", [(4, 3), (5, 10)])
def test_fp_add_same_sign_bit_exact(e_bits, m_bits):
    rng = np.random.default_rng(11)
    arr = fresh()
    E, M = e_bits, m_bits
    _, ea, ma = _fp_fields(N_COLS, E, M, rng)
    _, eb, mb = _fp_fields(N_COLS, E, M, rng)
    bias = (1 << (E - 1)) - 1
    ea = np.clip(ea, 2, bias + 2)
    eb = np.clip(eb, 2, bias + 2)
    r = 0
    def rows(k):
        nonlocal r
        out = list(range(r, r + k)); r += k
        return out
    ra_e, ra_m = rows(E), rows(M)
    rb_e, rb_m = rows(E), rows(M)
    ro_e, ro_m = rows(E), rows(M)
    scratch = rows(2 * (E + 1) + E + E + 2 * (M + 1) + E + (M + 3))
    layout.place(arr, ea, ra_e[0], E)
    layout.place(arr, ma, ra_m[0], M)
    layout.place(arr, eb, rb_e[0], E)
    layout.place(arr, mb, rb_m[0], M)
    prog = program.fp_add_same_sign(ra_e, ra_m, rb_e, rb_m, ro_e, ro_m,
                                    scratch, E, M)
    cyc = arr.run(prog)
    paper = timing.fp_add_cycles(E, M)
    assert abs(cyc - paper) <= max(10, int(0.5 * paper)), (cyc, paper)
    e_got = layout.extract(arr, ro_e[0], E, block=0)
    m_got = layout.extract(arr, ro_m[0], M, block=0)
    e_exp, m_exp = _fp_add_oracle(ea, ma, eb, mb, E, M)
    np.testing.assert_array_equal(m_got, m_exp)
    np.testing.assert_array_equal(e_got, e_exp)


# ---------------------------------------------------------------------------
# layout / swizzle (Sec. III-H)
# ---------------------------------------------------------------------------

def test_swizzle_roundtrip():
    rng = np.random.default_rng(3)
    for bits in (4, 8, 16):
        elems = rng.integers(0, 1 << bits, size=40)
        words = layout.swizzle(elems, bits)
        back = layout.unswizzle(words, bits)
        np.testing.assert_array_equal(back, elems)


def test_load_transposed_via_port():
    arr = fresh()
    rng = np.random.default_rng(4)
    vals = rng.integers(0, 256, size=160)
    layout.load_transposed(arr, 0, vals, base_row=0, n_bits=8)
    lanes = [layout.lane_of(j) for j in range(160)]
    got = layout.extract(arr, 0, 8, lanes=np.array(lanes), block=0)
    np.testing.assert_array_equal(got, vals)
    assert arr.io_words == timing.load_store_cycles(160, 8)


def test_hybrid_word_rw_roundtrip():
    arr = fresh()
    rng = np.random.default_rng(5)
    for _ in range(20):
        addr = int(rng.integers(0, 511))
        if addr == isa.INSTR_ADDR:
            continue
        w = int(rng.integers(0, 1 << 40))
        arr.write_word(0, addr, w)
        assert arr.read_word(0, addr) == w


def test_read_word_rejects_bad_addresses_like_write_word():
    """Regression: out-of-range reads indexed garbage rows instead of
    failing loudly; read_word now mirrors write_word's checks."""
    arr = fresh()
    for bad in (-1, isa.N_ROWS * isa.COL_MUX, isa.INSTR_ADDR):
        with pytest.raises(AssertionError):
            arr.read_word(0, bad)
        with pytest.raises(AssertionError):
            arr.write_word(0, bad, 1)
    assert arr.io_words == 0                   # nothing counted on failure


@pytest.mark.parametrize("bits", [4, 8, 16])
def test_place_matches_bit_transpose_ref(bits):
    """Transposed placement is the bit transpose: bit i of element j lands
    in row base + i, lane j (blocks side by side)."""
    arr = fresh(n_blocks=2)
    x = RNG.integers(0, 1 << bits, size=(2, N_COLS))
    layout.place(arr, x, 3, bits)
    planes = arr.mem[:, 3:3 + bits, :].transpose(1, 0, 2).reshape(bits, -1)
    np.testing.assert_array_equal(row_words(planes),
                                  ref.bit_transpose_ref(x.reshape(-1), bits))
    np.testing.assert_array_equal(layout.extract(arr, 3, bits), x)


def test_place_extract_roundtrip_signed():
    bits = 6
    x = RNG.integers(-(1 << (bits - 1)), 1 << (bits - 1), size=N_COLS)
    arr = fresh()
    layout.place(arr, x, 0, bits)
    np.testing.assert_array_equal(row_words(arr.mem[0, :bits, :]),
                                  ref.bit_transpose_ref(x, bits))
    np.testing.assert_array_equal(
        layout.extract(arr, 0, bits, block=0, signed=True), x)


def test_memory_mode_preserved_after_compute():
    """Hybrid mode: rows not touched by the program keep stored data."""
    arr = fresh()
    arr.write_word(0, 400, 0xDEADBEEF)
    a, b = rand_u(4), rand_u(4)
    layout.place(arr, a, 0, 4)
    layout.place(arr, b, 4, 4)
    arr.run(program.add(list(range(4)), list(range(4, 8)),
                        list(range(8, 13))))
    assert arr.read_word(0, 400) == 0xDEADBEEF
