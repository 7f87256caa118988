"""bank_host_ms.train: milliseconds per step in which the host was inside
the client bank: the union of the program's ``bank/*`` spans in the window
over the ``train_step`` executions there (``program_spans.host_ms``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.host_ms(ctx.trace)
