"""bank_dispatches.train: programs the client bank launches per step: the
host's program-execute events that start inside the program's ``bank/*``
spans in the window, over the ``train_step`` executions there
(``program_spans.dispatches``)."""
from chipbench import program_spans


def read(ctx):
    return program_spans.dispatches(ctx.trace)
