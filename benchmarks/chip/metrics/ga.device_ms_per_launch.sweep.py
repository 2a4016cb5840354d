"""Device ms of one GA program execution per chip (trace module line)."""
from layer_metrics import ga_ms_per_launch


def read(run):
    return ga_ms_per_launch(run)
