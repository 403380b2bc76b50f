"""Share of the window spent in grid dispatch on the host: uploading
state when it is not on the device and enqueueing the step kernel.  Sum
of ``grid.dispatch`` spans over the window (obs spans), in percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("grid.dispatch") or s.window_us <= 0:
        return None
    return 100.0 * s.total_us("grid.dispatch") / s.window_us
