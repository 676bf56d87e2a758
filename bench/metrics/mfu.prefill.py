"""Model step (api.prefill): operations of each window request's prefill,
active parameters only, over its host time (synchronous, so the device
work is inside) times the bf16 peak of every chip of the cell. Bounds the
flash kernel's share: a prefill without the kernel still reads here."""

from bench import counts


def read(run):
    if run.peaks is None:
        return None
    done = [t for t in run.requests if t.prefill]
    if not done:
        return None
    flops = sum(counts.prefill_flops(run.spec, t.prompt_len) for t in done)
    secs = sum(t.prefill[1] - t.prefill[0] for t in done)
    return flops / (secs * run.n_chips * run.peaks["bf16_flop_per_s"]) * 100.0
