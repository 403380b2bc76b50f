"""Share of the window the host spends waiting for the device before a
grid host sync copies the state back: sum of ``grid.wait`` spans over
the window (obs spans), in percent.  The rest of ``grid.host_sync`` is
the copy and the unpacking into bits."""


def read(run):
    s = run.spans
    if s is None or not s.named("grid.wait") or s.window_us <= 0:
        return None
    return 100.0 * s.total_us("grid.wait") / s.window_us
