"""Modelled CoMeFa cycles per fill of the Q6 scan: the query programs'
grid cycles (``comefa.kernel_cycles{kernel=q6_scan}``, the per-query
readout included) over the fills scanned (``scan.fills``).  A count of
the modelled design; a change that only speeds up the simulator leaves
it identical."""


def read(run):
    fills = run.counter("scan.fills")
    cycles = run.counter("comefa.kernel_cycles", kernel="q6_scan")
    if not fills or not cycles:
        return None
    return cycles / fills
