"""Share of the window spent in the Q6 queries' readouts: the in-grid
reduction's dispatch, the read of the partial sums and their sum on the
host, i.e. the ``scan.readout`` spans over the window (obs spans), in
percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("scan.readout") or s.window_us <= 0:
        return None
    return 100.0 * s.total_us("scan.readout") / s.window_us
