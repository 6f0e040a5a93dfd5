"""Device milliseconds a step of the owners' programs, both owners
together: ``tail`` (backward, update and the next forward, fused),
``head_fwd`` and ``head_bwd`` (a ``fit`` call's first forward and last
backward) and the row gather (``_lambda``), from the trace, over the
window's steps.  Moves ``train_samples_per_s``."""
from bench.trace import MissingModule

PROGRAMS = ("tail", "head_fwd", "head_bwd", "_lambda")


def read(ctx):
    steps = ctx["counters"]["steps"]
    try:
        return 1e3 * ctx["trace"].module_seconds(PROGRAMS) / steps
    except MissingModule:
        return None
