"""Share of the window spent copying grid state from the device back
to the host: sum of ``grid.host_sync`` spans over the window (obs spans),
in percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("grid.host_sync") or s.window_us <= 0:
        return None
    return 100.0 * s.total_us("grid.host_sync") / s.window_us
