"""Grid dispatches per served token: ``comefa.dispatches{kind=grid}``
over the window's completed steps, divided by their tokens (live rows).
A count: it repeats exactly while the program's chunking is unchanged."""


def read(run):
    tokens = run.facts.get("tokens")
    n = run.counter("comefa.dispatches", kind="grid")
    if not tokens or not n:
        return None
    return n / tokens
