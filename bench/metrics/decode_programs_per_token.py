"""Program runs (the trace's ``XLA Modules`` events on chip 0) that start
inside the window's ``decode.step`` spans, over the tokens those steps
generated (``harness.program_spans``)."""
from harness.program_spans import decode_programs_per_token as read  # noqa: F401
