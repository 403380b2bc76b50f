"""Host time per fill spent writing the fill's table rows into the
grid's device state: the mean length of a ``grid.write_rows`` span, in
microseconds (obs spans)."""


def read(run):
    s = run.spans
    if s is None:
        return None
    spans = s.named("grid.write_rows")
    if not spans:
        return None
    return sum(e.dur for e in spans) / len(spans)
