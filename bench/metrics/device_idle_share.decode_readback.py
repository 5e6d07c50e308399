"""Share of the window in which the chip was idle while a decode step read
its token back to the host: idle stretches under ``decode.readback``
(``harness.program_spans``), in %."""
from harness.program_spans import idle_by_program_span


def read(ctx):
    att = idle_by_program_span(ctx)
    return None if att is None else att.share(att.readback_ns)
