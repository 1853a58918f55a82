"""Seconds the set-up took to compile the program's train step, or to
load it from the persistent compilation cache, from the program's
compile counter (``harness.compiles``)."""
from harness import compiles


def read(ctx):
    group = compiles.setup_group(compiles.step_records())
    if group is None:
        return None
    return group[2]["seconds"]
