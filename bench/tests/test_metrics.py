"""The per-layer readers of the grid's host buckets, on synthetic obs
spans and counters: each gives its value, and nothing when the program
recorded none of what it reads."""
import pytest

import benchlib
from conftest import BENCH
from repro.obs.trace import TraceEvent


def _ev(name, ts, dur, **attrs):
    return TraceEvent(name, "wall", 1, float(ts), float(dur), attrs)


def _view(events, counters=None, window=(0.0, 1000.0), **facts):
    return benchlib.RunView(
        spans=benchlib.Spans(events, *window), counters=counters or {},
        device=None, peaks={}, facts=facts)


def _read(name, run):
    return benchlib.load_module(BENCH / "metrics" / f"{name}.py").read(run)


# one serving window of 1000 us: a placement holding a host sync (and
# its wait), a dispatch holding an upload, then the extract with a sync
SERVE = [
    _ev("kernel.gemv_batched", 0, 600),
    _ev("kernel.place", 10, 200),
    _ev("grid.host_sync", 50, 100),
    _ev("grid.wait", 60, 30),
    _ev("grid.dispatch", 300, 150),
    _ev("grid.upload", 310, 40),
    _ev("grid.program_upload", 360, 5),
    _ev("kernel.extract", 700, 100),
    _ev("grid.host_sync", 720, 50),
    _ev("grid.wait", 725, 10),
]


def test_layout_host_share():
    # place 200 - sync 100, extract 100 - sync 50: 150 us of 1000
    assert _read("layout_host_share", _view(SERVE)) == pytest.approx(15.0)


def test_device_wait_share():
    assert _read("device_wait_share", _view(SERVE)) == pytest.approx(4.0)


def test_state_upload_share():
    assert _read("state_upload_share", _view(SERVE)) == pytest.approx(4.0)


def test_host_bytes_per_tok():
    counters = {
        "comefa.transfer_bytes{dir=h2d,kind=grid,what=state}": 5200.0,
        "comefa.transfer_bytes{dir=d2h,kind=grid,what=state}": 5200.0,
        "comefa.transfer_bytes{dir=h2d,kind=grid,what=program}": 600.0,
        "comefa.transfer_bytes{dir=h2d,kind=array,what=state}": 999.0,
    }
    run = _view(SERVE, counters, tokens=4)
    assert _read("host_bytes_per_tok", run) == pytest.approx(2750.0)


# two per-slot dispatches: stacks of 30 and 50 us, uploads of 12 and 8
PERSLOT = [
    _ev("grid.run_per_slot", 0, 100, padded_to=32),
    _ev("grid.stack", 5, 30),
    _ev("grid.program_upload", 40, 12),
    _ev("grid.run_per_slot", 200, 120, padded_to=32),
    _ev("grid.stack", 205, 50),
    _ev("grid.program_upload", 260, 8),
    _ev("grid.stack", 500, 70),            # outside any dispatch
]


def test_slot_stack_us():
    assert _read("slot_stack_us.fleet", _view(PERSLOT)) == \
        pytest.approx(40.0)


def test_program_upload_us():
    assert _read("program_upload_us.fleet", _view(PERSLOT)) == \
        pytest.approx(10.0)


@pytest.mark.parametrize("name", [
    "layout_host_share", "device_wait_share", "state_upload_share",
    "host_bytes_per_tok", "slot_stack_us.fleet", "program_upload_us.fleet"])
def test_nothing_without_the_spans(name):
    """A program older than these spans and counters reads nothing, and
    does not raise."""
    bare = [_ev("kernel.gemv_batched", 0, 600), _ev("grid.dispatch", 10, 50),
            _ev("grid.run_per_slot", 100, 50, padded_to=32)]
    counters = {"comefa.dispatches{engine=pallas,kind=grid}": 2.0}
    assert _read(name, _view(bare, counters, tokens=4)) is None
    assert _read(name, benchlib.RunView(spans=None, counters={}, device=None,
                                        peaks={}, facts={})) is None
