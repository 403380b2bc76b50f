"""Share of the window in the batched GEMV kernel's own host work: the
per-chunk ``layout.place`` of weights and activation bits and the final
``layout.extract``.  Sum of ``kernel.gemv_batched`` spans less their
``grid.*`` children (dispatch, host sync), over the window (obs spans),
in percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("kernel.gemv_batched") or s.window_us <= 0:
        return None
    own = s.self_us("kernel.gemv_batched",
                    lambda e: e.name.startswith("grid."))
    return 100.0 * own / s.window_us
