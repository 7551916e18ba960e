"""Decode step, whole: model FLOPs of the decode steps in the traced part
(matrix products of each token and attention over the selected pages, as
the architecture's adapter counts them: ``run.work.decode_flops``) over the
traced window times the chip's peak FLOP/s, in %."""


def read(run):
    if run.trace is None or not run.trace.chips or not run.traced_steps:
        return None
    first, last = run.trace.steps[0], run.trace.steps[len(run.traced_steps) - 1]
    window = (last.end - first.start) / 1e9
    flops = sum(run.work.decode_flops(s.cur_lens) for s in run.traced_steps)
    return 100.0 * flops / (window * run.peak["flops_per_s"])
