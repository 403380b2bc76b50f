"""Share of the executed per-slot program cycles that are padding: each
slice's own program length (`run_per_slot`'s returned counts) against
the stack every slice executes, ``padded_to`` (span attribute).
1 - sum of own cycles / (slices x padded_to), in percent.  A count."""


def read(run):
    s = run.spans
    own = run.facts.get("own_cycles")
    if s is None or not own or not run.facts.get("per_slot"):
        return None
    spans = s.named("grid.run_per_slot")
    if not spans:
        return None
    padded = int(spans[-1].attrs["padded_to"])
    return 100.0 * (1.0 - sum(own) / (len(own) * padded))
