"""Device idle ms inside each launch's dse.dispatch span, mean per launch."""
from program_spans import idle_in_dispatch_ms


def read(run):
    return idle_in_dispatch_ms(run)
