"""Bytes on the wire per step at the split boundary, cut activations
and cut gradients of every owner: the transport's own count
(``transport_stats["total_wire_bytes"] / steps``).  Moves
``train_samples_per_s``."""


def read(ctx):
    t = ctx["counters"]["transport"]
    if not t.get("steps"):
        return None
    return t["total_wire_bytes"] / t["steps"]
