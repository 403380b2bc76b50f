"""Share of the window outside the grid-executed projections: the
serving host loop (admission, sampling) and the eager decode step around
the projections.  1 - sum of ``serve.grid_linear`` spans / window (obs
spans, host clock), in percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("serve.grid_linear") or s.window_us <= 0:
        return None
    return 100.0 * (1.0 - s.total_us("serve.grid_linear") / s.window_us)
