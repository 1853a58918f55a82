"""Seconds the set-up took to trace the program's train step to a jaxpr
and lower it to an MLIR module, from the program's compile counter
(``harness.compiles``)."""
from harness import compiles


def read(ctx):
    group = compiles.setup_group(compiles.step_records())
    if group is None:
        return None
    trace_, lower, _ = group
    return trace_["seconds"] + lower["seconds"]
