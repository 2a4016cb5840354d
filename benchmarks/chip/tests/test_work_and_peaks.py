"""The work count behind ``ga_roofline`` and the table of peaks."""
import inspect
import json

import pytest

import harness
import work_count
from conftest import BENCH


def test_launch_bytes_depends_only_on_the_launch_shape():
    assert list(inspect.signature(work_count.launch_bytes).parameters) == \
        ["S", "P", "G", "W", "n"]
    # one slot, one workload, no generation: P designs read their genome
    # and 7 statistics and write a score
    assert work_count.launch_bytes(1, 10, 0, 1, 9) == 10 * (10 * 4 + 7 * 4)
    # linear in slots and workloads, exactly
    a = work_count.launch_bytes(64, 1024, 100, 114, 9)
    b = work_count.launch_bytes(128, 1024, 100, 228, 9)
    assert b == 2 * a
    # a generation adds the population's read and write and the survival
    # read of 2P candidates
    g1 = work_count.launch_bytes(1, 8, 1, 1, 9) - work_count.launch_bytes(1, 8, 0, 1, 9)
    assert g1 == 8 * (10 * 4 + 7 * 4) + 4 * 8 * 10 * 4


def test_sweep_launch_needs_about_a_gigabyte():
    # 64 slots cycling the paper mix (16 workloads per 9 requests)
    need = work_count.launch_bytes(64, 1024, 100, 114, 9)
    assert 0.5e9 < need < 3e9


def test_peaks_table_holds_the_published_v5e_numbers():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"] and "Google Cloud" in peaks["source"]
    v5e = harness.peak_for(peaks, "TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_is_an_error():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peak_for(peaks, "TPU v9 imaginary")
