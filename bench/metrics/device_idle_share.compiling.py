"""Share of the window in which the chip was idle while the program was
compiling: idle stretches whose midpoint falls in a ``compile`` span
(``harness.program_spans``), in %."""
from harness.program_spans import idle_by_program_span


def read(ctx):
    att = idle_by_program_span(ctx)
    return None if att is None else att.share(att.compiling_ns)
