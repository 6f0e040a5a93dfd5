"""Model FLOP/s utilization of the traced window: the model FLOPs of a
step (``bench.flops``, with the choices routed to held experts as the
program counted them) times the window's steps, over the window's
seconds and the chip's bf16 peak.  Moves ``train_samples_per_s``."""
from bench import flops


def read(ctx):
    c = ctx["counters"]
    moe = c["transport"].get("moe")
    if not moe or not c["steps"]:
        return None
    per_step = flops.mla_moe_split_step_flops(
        ctx["config"], ctx["traffic"], moe["routed"] / moe["steps"])
    return 100.0 * c["steps"] * per_step / c["window_s"] \
        / ctx["peaks"]["bf16_flops"]
