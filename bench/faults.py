"""Faults planted under the timed path, to show the check catches them.

``run.py --fault <name>`` installs one after set-up, so it breaks the
timed window alone; the benchmark's own runs never do.  Each breaks what
a later change could break by mistake:

  * ``state_unchanged``: every grid dispatch returns its state as it came;
  * ``half_batch``: a dispatch computes only the first half of the grid's
    slots (requests, FSM slices) and leaves the rest as they were;
  * ``answer_altered``: answers altered where they are produced - every
    served token (serving), or one lane of one slice's state (fleet).
"""
from __future__ import annotations

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def _patch_engines(wrap) -> None:
    from repro.core.comefa import engine_packed as ep
    for cls in (ep.PackedXlaEngine, ep.PallasEngine):
        for meth in ("run", "run_per_slot"):
            orig = cls.__dict__.get(meth)
            if orig is not None:
                setattr(cls, meth, wrap(orig))


def install(name: str, system_kind: str) -> None:
    import jax.numpy as jnp
    if name not in FAULTS:
        raise SystemExit(f"bench: unknown fault {name!r}")

    if name == "state_unchanged":
        _patch_engines(lambda orig: lambda self, state, *a: state)
    elif name == "half_batch":
        def wrap(orig):
            def run(self, state, *a):
                out = orig(self, state, *a)
                if out[0].ndim < 4:              # a single array, no slots
                    return out
                half = out[0].shape[0] // 2
                return tuple(
                    jnp.where((jnp.arange(o.shape[0]) < half).reshape(
                        (-1,) + (1,) * (o.ndim - 1)), o, s)
                    for o, s in zip(out, state))
            return run
        _patch_engines(wrap)
    elif system_kind == "serve":
        from repro.serve import engine as serve_engine
        orig = serve_engine._sample_token

        def sample(logits_row, key, temperature):
            return (orig(logits_row, key, temperature) + 1) % \
                logits_row.shape[-1]
        serve_engine._sample_token = sample
    else:
        flipped = []

        def wrap(orig):
            def run(self, state, *a):
                mem, carry, mask = orig(self, state, *a)
                if mem.ndim == 4 and not flipped:
                    # lane 0 of slice 0, block 0: operand and product rows
                    flipped.append(True)
                    mem = mem.at[0, 0, :64, 0].set(mem[0, 0, :64, 0] ^ 1)
                return mem, carry, mask
            return run
        _patch_engines(wrap)
