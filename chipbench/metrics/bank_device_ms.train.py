"""bank_device_ms.train: device time per step of every program the training
loop runs besides the jitted step: the client bank's cohort gather and
scatter, each one compiled program over the whole client tree."""
from chipbench import trace

STEP = "train_step"


def read(ctx):
    s = trace.module_s(ctx.trace, {STEP}, exclude=True)
    n = trace.module_counts(ctx.trace).get(STEP)
    if s is None or not n:
        return None
    return 1e3 * s / n
