"""mfu.offline: the least time the chip could take for the window's work
over the window, in percent. Each decode step and each prefill counts the
larger of its FLOPs at the peak and its bytes (weights, KV read and written)
at the HBM bandwidth (``flops.decode_step``, ``flops.prefill``)."""
from chipbench import flops


def read(ctx):
    c = ctx.counts
    steps = [s for s in c.get("decode_contexts", []) if s]
    if not steps:
        return None
    least = sum(flops.least_s(*flops.decode_step(ctx.config, s), ctx.peak)
                for s in steps)
    least += sum(flops.least_s(*flops.prefill(ctx.config, L), ctx.peak)
                 for L in c["prefill_lens"])
    return 100.0 * least / c["window_s"]
