"""Share of the window spent packing the grid state and uploading it to
the device before a dispatch: sum of ``grid.upload`` spans over the
window (obs spans), in percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("grid.upload") or s.window_us <= 0:
        return None
    return 100.0 * s.total_us("grid.upload") / s.window_us
