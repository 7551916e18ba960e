"""Kernels: ``kernels/paged_attention.py``'s share of its roofline in the
traced decode steps: the least time its counted work needs at the chip's
peaks (the selected pages of live lanes, q and output, over every layer,
as the architecture's adapter counts them: ``run.work.attention``) over its
measured device time, in %."""

KERNEL = "paged_attention"


def read(run):
    if run.trace is None or not run.traced_steps:
        return None
    n = len(run.traced_steps)
    per_step = run.trace.step_seconds(run.trace.ops, lambda name: KERNEL in name)[:n]
    seconds = sum(per_step)
    if seconds <= 0:
        return None
    flops = nbytes = 0.0
    for s in run.traced_steps:
        f, b = run.work.attention(s.cur_lens)
        flops += f
        nbytes += b
    return run.counts.roofline_share(flops, nbytes, seconds, run.peak)[0]
