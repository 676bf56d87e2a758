"""kernels/flash_attention: the least time the chip could take for the
traced prefills' flash-attention calls (the larger of operations over the
bf16 peak and bytes over HBM bandwidth, per call), over the device time of
the kernel's events in the trace. None where the trace shows no such event."""

from bench import counts, tracefile

PATTERN = "flash_attention"


def read(run):
    if not run.trace or run.peaks is None or not run.flash_prefill:
        return None
    secs, n = tracefile.kernel_seconds(run.trace, PATTERN)
    if n == 0 or secs <= 0:
        return None
    calls = [p.S for p in run.prefills if p.S % 128 == 0 and run.traced(p.t0, p.t1)]
    if not calls:
        return None
    least = sum(counts.flash_attention_call(run.spec, S).min_seconds(run.peaks) for S in calls) * run.spec.n_layers
    return least / secs * 100.0
