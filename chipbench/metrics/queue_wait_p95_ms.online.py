"""queue_wait_p95_ms.online: 95th percentile, over the requests admitted in
the window, of the host time from a request's due time to the start of the
engine step that admitted it."""
import numpy as np


def read(ctx):
    w = ctx.counts.get("queue_wait_s")
    if not w:
        return None
    return 1e3 * float(np.percentile(w, 95))
