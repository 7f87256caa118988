"""Device time of the engine's decode ``step`` program per execution."""
from chipbench import trace


def read(ctx):
    s = trace.module_s(ctx.trace, {"step"})
    n = trace.module_counts(ctx.trace).get("step")
    if s is None or not n:
        return None
    return 1e3 * s / n
