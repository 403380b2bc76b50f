"""Modelled CoMeFa cycles per served token: the batched GEMV's grid
cycles (``comefa.kernel_cycles{kernel=gemv_batched}``) over the window's
completed steps, divided by their tokens.  A count of the modelled
design; a change that only speeds up the simulator leaves it identical."""


def read(run):
    tokens = run.facts.get("tokens")
    cycles = run.counter("comefa.kernel_cycles", kernel="gemv_batched")
    if not tokens or not cycles:
        return None
    return cycles / tokens
