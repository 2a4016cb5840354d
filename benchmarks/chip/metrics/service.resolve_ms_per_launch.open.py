"""Host ms per launch in the dse.resolve span."""
from program_spans import span_ms_per_launch


def read(run):
    return span_ms_per_launch(run, "dse.resolve")
