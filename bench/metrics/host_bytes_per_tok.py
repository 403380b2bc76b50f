"""Bytes that cross between host and device for the grid per served
token: ``comefa.transfer_bytes{kind=grid}`` (state and programs, both
directions) over the window's completed steps, divided by their tokens
(live rows).  A count: it repeats exactly while the chunking and the
state's layout are unchanged."""


def read(run):
    tokens = run.facts.get("tokens")
    n = run.counter("comefa.transfer_bytes", kind="grid")
    if not tokens or not n:
        return None
    return n / tokens
