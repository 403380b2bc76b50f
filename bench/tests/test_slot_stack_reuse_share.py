"""The reader of per-slot stack reuse, on synthetic obs counters: its
share, and nothing when the program counted no per-slot stacks."""
import pytest

import benchlib
from conftest import BENCH


def _read(counters):
    view = benchlib.RunView(spans=None, counters=counters, device=None,
                            peaks={}, facts={"dispatches": 4})
    reader = benchlib.load_module(BENCH / "metrics" /
                                  "slot_stack_reuse_share.py")
    return reader.read(view)


def test_slot_stack_reuse_share():
    counters = {"comefa.slot_stack{event=reuse}": 99.0,
                "comefa.slot_stack{event=build}": 1.0,
                "comefa.dispatches{engine=pallas,kind=grid}": 100.0}
    assert _read(counters) == pytest.approx(99.0)
    assert _read({"comefa.slot_stack{event=reuse}": 7.0}) == 100.0
    assert _read({"comefa.slot_stack{event=build}": 3.0}) == 0.0


def test_slot_stack_reuse_share_reads_nothing_without_the_counter():
    """A program older than the counter reads nothing, and does not raise."""
    assert _read({"comefa.dispatches{engine=pallas,kind=grid}": 2.0}) is None
    assert _read({}) is None
