"""The sweep's work count: the algorithm's, not a formulation's."""
import json

import pytest

from harness import work
from harness.cell import PEAKS


def test_segment_work_counts_events_voxels_and_maps():
    ops, nbytes = work.segment_work(frames=2, events_per_frame=4, planes=3,
                                    height=5, width=7, quantized=True)
    events, voxels = 8, 3 * 5 * 7
    assert ops == events * (14 + 7 * 3) + 2 * (54 + 10 * 3) + voxels * 2
    assert nbytes == events * 9 + 2 * 48 + voxels * 2 + 5 * 7 * 9
    _, wide = work.segment_work(2, 4, 3, 5, 7, quantized=False)
    assert wide - nbytes == voxels * 2  # int32 store instead of int16


def test_no_formulation_work_is_counted():
    """The one-hot matmul does h*w MACs per vote; the count grows with
    events x planes only, so it stays far below that."""
    ops, _ = work.segment_work(267, 1024, 128, 180, 240, quantized=True)
    matmul_macs = 267 * 1024 * 128 * 180 * 240
    assert ops < matmul_macs / 1000


def test_paper_segment_is_memory_bound_on_v5e():
    peak = json.loads(PEAKS.read_text())["TPU v5 lite"]
    ops, nbytes = work.segment_work(267, 1024, 128, 180, 240, quantized=True)
    t, bound = work.least_time_s(ops, nbytes, peak)
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    assert 1e-5 < t < 1e-4  # ~18 us of algorithmic bytes per segment


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = json.loads(PEAKS.read_text())
    for kind, p in peaks.items():
        assert p["ops_per_s"] > 0 and p["hbm_bytes_per_s"] > 0 and p["source"]
