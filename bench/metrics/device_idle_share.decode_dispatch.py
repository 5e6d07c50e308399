"""Share of the window in which the chip was idle inside a decode step
while the host prepared and launched its work: idle stretches under
``decode.step``, less those under ``decode.readback`` or ``compile``
(``harness.program_spans``), in %."""
from harness.program_spans import idle_by_program_span


def read(ctx):
    att = idle_by_program_span(ctx)
    return None if att is None else att.share(att.dispatch_ns)
