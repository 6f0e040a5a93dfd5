"""Rows of the held experts' buffer, over which the gather, the masks,
the activation and the scatter run, over the token choices routed to
them (1 = no padding): the program's share-layer
counters (``transport_stats["moe"]``), summed over the MoE layers and the
window's steps.  Moves ``train_samples_per_s``."""


def read(ctx):
    moe = ctx["counters"]["transport"].get("moe")
    return None if not moe else moe["rows_per_routed"]
