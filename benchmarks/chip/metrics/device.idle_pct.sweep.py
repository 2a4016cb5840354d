"""Share of the traced window with no operation on the device, averaged over chips."""
from layer_metrics import idle_pct


def read(run):
    return idle_pct(run)
