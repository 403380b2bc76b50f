"""Share of the window's batched GEMV calls that found their weights'
bit planes already on the device: ``comefa.weight_planes{event=reuse}``
over ``reuse`` plus ``build`` (program counter), in percent.  Every
matrix is built in the warm-up, so a window that builds none reads 100."""


def read(run):
    reuse = run.counter("comefa.weight_planes", event="reuse")
    build = run.counter("comefa.weight_planes", event="build")
    if reuse + build <= 0:
        return None
    return 100.0 * reuse / (reuse + build)
