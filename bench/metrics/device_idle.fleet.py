"""Share of the traced window in which no operation ran on the device
(profiler trace), fleet cells.  Percent."""


def read(run):
    dev = run.device
    if dev is None or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
