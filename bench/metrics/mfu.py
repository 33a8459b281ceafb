"""Model FLOP/s utilization of local training over the window: trained
samples x training FLOPs per sample (the reference model's shapes; the
clients' loss evaluations are not counted) / (window seconds x the chips'
bf16 peak), in percent."""
from fedbench import probes


def read(ctx: dict) -> float | None:
    samples = ctx["spans"].counted(probes.TRAIN, ctx["t0"], ctx["t1"])
    if not samples:
        return None
    flops = samples * ctx["train_flops_per_sample"]
    return 100.0 * flops / ((ctx["t1"] - ctx["t0"]) * ctx["peak_flops_per_s"])
