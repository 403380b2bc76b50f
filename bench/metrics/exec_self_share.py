"""Share of the window in the grid executor itself: quantize, offset
encode, zero-point corrections and dequantize.  Sum of
``serve.grid_linear`` spans less their ``kernel.gemv_batched`` children,
over the window (obs spans), in percent."""


def read(run):
    s = run.spans
    if s is None or not s.named("serve.grid_linear") or s.window_us <= 0:
        return None
    own = s.self_us("serve.grid_linear",
                    lambda e: e.name == "kernel.gemv_batched")
    return 100.0 * own / s.window_us
