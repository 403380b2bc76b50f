"""Share of the window in the batched GEMV's layout work on the host:
placing each tile's weights and activation bits (``kernel.place``) and
reading the accumulators out (``kernel.extract``), less the grid host
syncs inside them.  Sum of the self times of both spans over the window
(obs spans), in percent."""


def _grid(e):
    return e.name.startswith("grid.")


def read(run):
    s = run.spans
    if s is None or s.window_us <= 0 or not (
            s.named("kernel.place") or s.named("kernel.extract")):
        return None
    own = s.self_us("kernel.place", _grid) + s.self_us("kernel.extract", _grid)
    return 100.0 * own / s.window_us
