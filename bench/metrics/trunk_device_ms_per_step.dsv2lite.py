"""Device milliseconds a step of the scientist's programs: ``cutgrad``,
``weightgrad`` and ``upd`` (the trunk's update; the owners run it too,
once, at the last step of a ``fit`` call), from the trace, over the
window's steps.  Moves ``train_samples_per_s``."""
from bench.trace import MissingModule

PROGRAMS = ("cutgrad", "weightgrad", "upd")


def read(ctx):
    steps = ctx["counters"]["steps"]
    try:
        return 1e3 * ctx["trace"].module_seconds(PROGRAMS) / steps
    except MissingModule:
        return None
