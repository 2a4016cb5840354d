"""The GA launch's least time at the published HBM bandwidth over its device time, in percent."""
from layer_metrics import ga_roofline_pct


def read(run):
    return ga_roofline_pct(run)
