"""Host time per grid dispatch in the fleet cells: the mean length of a
``grid.dispatch`` span (shared program) or ``grid.run_per_slot`` span
(one program per slice, its stack built and uploaded each time), in
microseconds (obs spans)."""


def read(run):
    s = run.spans
    if s is None:
        return None
    name = "grid.run_per_slot" if run.facts["per_slot"] else "grid.dispatch"
    spans = s.named(name)
    if not spans:
        return None
    return sum(e.dur for e in spans) / len(spans)
