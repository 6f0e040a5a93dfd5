"""Share of the traced window in which the device ran no operation:
1 - busy / window, from the trace (busy is the union of operation
intervals).  Moves ``train_samples_per_s``."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
