"""prefill_device_ms.online: device time of the ``prefill`` and ``adopt``
programs per admitted request."""
from chipbench import trace


def read(ctx):
    s = trace.module_s(ctx.trace, {"prefill", "adopt"})
    n = trace.module_counts(ctx.trace).get("prefill")
    if s is None or not n:
        return None
    return 1e3 * s / n
