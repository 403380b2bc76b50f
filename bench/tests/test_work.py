"""The yardstick's arithmetic: bytes and FLOPs from shapes, and the
traffic generator's sizes."""
import numpy as np
import pytest

import loadgen
import work


def test_step_kernel_bytes_by_hand():
    # 2 slots x 1 block: mem 2*1*128*5 words, carry and mask 2*1*5 each,
    # read and written once; a 280 x 10 shared program read once
    state = (2 * 128 * 5 + 2 * 2 * 5) * 4
    assert work.step_kernel_bytes(2, 1, 280, 10, per_slot=False) == \
        2 * state + 280 * 10 * 4
    # per-slot programs: one [T, F] matrix per slot
    assert work.step_kernel_bytes(2, 1, 288, 10, per_slot=True) == \
        2 * state + 2 * 288 * 10 * 4


def test_step_kernel_bytes_fleet_size():
    """The fleet's state is ~3.9 MB each way (the figure the issue and
    PERF.md quote)."""
    b = work.step_kernel_bytes(66, 23, 280, 10, per_slot=False)
    assert 2 * 3.8e6 < b < 2 * 4.0e6


def test_packed_words():
    assert work.packed_words(160) == 5
    assert work.packed_words(32) == 1
    assert work.packed_words(33) == 2


def test_lm_flops_per_token_by_hand():
    # d=2, f=3, 1 head of 2, 1 kv head, 1 layer, vocab 5, context 4
    proj = 2 * 2 + 2 * 2 * 2 + 2 * 2 + 3 * 2 * 3     # q, k+v, o, 3 mlp
    attn = 2 * 1 * 2 * 4                             # scores + sum
    head = 2 * 5
    assert work.lm_flops_per_token(
        hidden=2, intermediate=3, heads=1, kv_heads=1, head_dim=2,
        layers=1, vocab=5, context=4) == 2.0 * (proj + attn + head)


def test_lm_flops_smollm_layer():
    """One smollm-360m layer plus its tied head: ~2 x 11.1 M + 2 x 47 M."""
    f = work.lm_flops_per_token(
        hidden=960, intermediate=2560, heads=15, kv_heads=5, head_dim=64,
        layers=1, vocab=49152, context=0)
    assert f == 2.0 * (960 * 960 * 2 + 2 * 960 * 320 + 3 * 960 * 2560
                       + 960 * 49152)


@pytest.mark.parametrize("dist", [
    {"dist": "loguniform", "lo": 4, "hi": 64},
    {"dist": "uniform_int", "lo": 4, "hi": 16},
    {"dist": "fixed", "value": 16}])
def test_sizes_in_range_and_same_every_seed(dist):
    s = loadgen.sizes(dist, 257)
    lo = dist.get("lo", dist.get("value"))
    hi = dist.get("hi", dist.get("value"))
    assert s.min() >= lo and s.max() <= hi
    if "hi" in dist:
        assert s.min() == lo and s.max() == hi


def test_requests_same_work_every_seed():
    mix = {"kind": "requests", "loop": "closed", "requests": 64,
           "max_len": 96,
           "prompt_len": {"dist": "loguniform", "lo": 4, "hi": 64},
           "output_len": {"dist": "loguniform", "lo": 4, "hi": 32}}
    a = loadgen.requests(mix, 1, 100)
    b = loadgen.requests(mix, 2 ** 33 + 5, 100)
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in b)
    assert sorted(n for _, n in a) == sorted(n for _, n in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    again = loadgen.requests(mix, 1, 100)
    assert all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, again))
    # a mix asking for what the generator does not make is refused
    with pytest.raises(SystemExit):
        loadgen.requests(dict(mix, loop="open"), 1, 100)


def test_grid_widths_and_operands():
    mix = {"kind": "grid_ops", "per_slot": True,
           "bits": {"dist": "uniform_int", "lo": 4, "hi": 16}}
    w = loadgen.grid_widths(mix, 9, 66)
    assert sorted(w) == sorted(loadgen.grid_widths(mix, 10, 66))
    a, b = loadgen.grid_operands(9, w, 2, 160)
    assert a.shape == (66, 2, 160)
    assert (a < (1 << w[:, None, None])).all() and (b >= 0).all()
    shared = dict(mix, per_slot=False, bits={"dist": "fixed", "value": 16})
    assert set(loadgen.grid_widths(shared, 9, 66)) == {16}
