"""The bytes a GA launch must move, from its shape alone.

Counted from what the search needs, not from how the program does it:

* every design evaluated reads its genome (n float32), and for each of its
  W workloads the workload's 7 sufficient statistics of the cost model
  (crossbar demand, DAC drives and DRAM spill at the design's grid cell,
  and the four layer sums), and writes its score (one float32);
* every generation reads and writes the population and its scores once
  (P designs of n + 1 float32 each way), and its survival reads the 2P
  candidates once.

A launch of S slots evaluates (G + 1) * P designs per slot.  ``W`` is the
launch's workloads summed over its slots.  Not counted: a history buffer,
sort passes, padding slots, or any other choice of an implementation, so
a rewrite is measured against the same work.

The GA does no matrix work, so its bound is memory bandwidth:
``least_seconds = launch_bytes / peak bytes/s``.
"""
from __future__ import annotations

F32 = 4
STATS_PER_WORKLOAD = 7


def launch_bytes(S: int, P: int, G: int, W: int, n: int) -> int:
    """Required bytes of one launch: S slots of a (P, G) search over n
    genes, W workloads over all slots together."""
    designs_per_slot = (G + 1) * P
    per_design = (n + 1) * F32
    evals = S * designs_per_slot * per_design \
        + W * designs_per_slot * STATS_PER_WORKLOAD * F32
    row = (n + 1) * F32
    generations = S * G * (2 * P * row + 2 * P * row)
    return int(evals + generations)
