"""Model step (api.prefill, api.decode_step): operations of the prompt and
output tokens processed in the window, active parameters only, attention
over the live context, over the window times the bf16 peak of every chip
of the cell."""

from bench import counts


def read(run):
    if run.peaks is None:
        return None
    s = run.spec
    flops = sum(counts.prefill_flops(s, p.S) for p in run.prefills if run.in_window(p.t1))
    flops += sum(counts.decode_token_flops(s, c) for d in run.decodes if run.in_window(d.t1) for c in d.contexts)
    return flops / (run.seconds * run.n_chips * run.peaks["bf16_flop_per_s"]) * 100.0
