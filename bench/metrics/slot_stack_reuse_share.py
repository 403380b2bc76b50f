"""Share of the window's ``run_per_slot`` dispatches that reused the
frozen stack of an earlier identical program list:
``comefa.slot_stack{event=reuse}`` over ``reuse`` plus ``build``
(program counter), in percent.  A program without the counter reads
nothing."""


def read(run):
    reuse = run.counter("comefa.slot_stack", event="reuse")
    build = run.counter("comefa.slot_stack", event="build")
    if reuse + build <= 0:
        return None
    return 100.0 * reuse / (reuse + build)
