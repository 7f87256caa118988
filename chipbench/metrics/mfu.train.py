"""mfu.train: the step's needed FLOPs (``flops.train_step_flops``) over the
device time of one ``train_step`` program (the trace's time of that program
in the window over its executions) times the chip's peak, in percent."""
from chipbench import trace

STEP = "train_step"


def read(ctx):
    s = trace.module_s(ctx.trace, {STEP})
    n = trace.module_counts(ctx.trace).get(STEP)
    if s is None or not n:
        return None
    return 100.0 * ctx.counts["step_flops"] * n / (s * ctx.peak["flops_per_s"])
