"""The reader of resident weight planes, on synthetic obs counters: its
share, and nothing when the program counted no weight planes."""
import pytest

import benchlib
from conftest import BENCH


def _read(counters):
    view = benchlib.RunView(spans=None, counters=counters, device=None,
                            peaks={}, facts={"tokens": 4})
    reader = benchlib.load_module(BENCH / "metrics" / "weight_reuse_share.py")
    return reader.read(view)


def test_weight_reuse_share():
    counters = {"comefa.weight_planes{event=reuse}": 21.0,
                "comefa.weight_planes{event=build}": 7.0,
                "comefa.dispatches{engine=pallas,kind=grid}": 9.0}
    assert _read(counters) == pytest.approx(75.0)
    assert _read({"comefa.weight_planes{event=reuse}": 7.0}) == 100.0


def test_weight_reuse_share_reads_nothing_without_the_counter():
    """A program older than the counter reads nothing, and does not raise."""
    assert _read({"comefa.dispatches{engine=pallas,kind=grid}": 2.0}) is None
    assert _read({}) is None
