"""Host ms per launch in the dse.harvest.finalize span."""
from program_spans import span_ms_per_launch


def read(run):
    return span_ms_per_launch(run, "dse.harvest.finalize")
