"""Host time per ``run_per_slot`` dispatch spent encoding the slices'
programs and padding them into one ``[G, T, F]`` stack: the
``grid.stack`` spans inside each ``grid.run_per_slot`` span, summed and
averaged over those dispatches, in microseconds (obs spans)."""


def read(run):
    s = run.spans
    if s is None:
        return None
    parents = s.named("grid.run_per_slot")
    inner = [c for p in parents
             for c in s.children(p, lambda e: e.name == "grid.stack")]
    if not inner:
        return None
    return sum(e.dur for e in inner) / len(parents)
