"""bank_idle_ms.train: milliseconds per step in which the device waits on
the client bank: time inside the program's ``bank/*`` spans in which no
operation ran on the device, over the ``train_step`` executions in the
window (``program_spans.idle_ms``). At most ``bank_host_ms.train``."""
from chipbench import program_spans


def read(ctx):
    return program_spans.idle_ms(ctx.trace)
