"""Model step (api.loss_fn through Trainer): forward and backward
operations per token from shapes, no recomputation, times train tokens
per second, over the bf16 peak of every chip of the cell."""

from bench import counts


def read(run):
    if run.peaks is None or run.kind != "train":
        return None
    t = run.train
    flops = counts.train_step_flops(run.spec, t["batch"], t["seq"]) * t["steps"]
    return flops / (run.seconds * run.n_chips * run.peaks["bf16_flop_per_s"]) * 100.0
