"""Work and byte counts pinned on the paper network, and the peaks table."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import work  # noqa: E402

PAPER = (768, 256, 256, 256, 10)


def test_paper_network_counts():
    assert work.macs_per_inference(PAPER) == 330_240
    assert work.weight_bytes(PAPER) == 41_280
    assert work.learn_macs_per_sample(PAPER) == 330_240


def test_bucket_128_cascade_is_compute_bound():
    ops, nbytes = work.cascade_work(PAPER, 128)
    assert ops == 2 * 128 * 330_240 == 84_541_440
    assert nbytes == 128 * 96 + 41_280 + 128 * 10 * 4 == 58_688
    t, bound = work.least_time(ops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert t == pytest.approx(84_541_440 / 393e12)
    assert t == pytest.approx(0.2151e-6, rel=1e-3)


def test_small_bucket_is_memory_bound():
    ops, nbytes = work.cascade_work(PAPER, 8)
    t, bound = work.least_time(ops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_temporal_and_stdp_counts():
    ops, nbytes = work.temporal_work(PAPER, 64, 16)
    assert ops == 2 * 64 * 16 * 330_240
    assert nbytes == 64 * 16 * 96 + 41_280 + 64 * 40
    assert work.stdp_work(256) == (512, 96)


def test_peaks_table_has_its_source_and_refuses_unknown_kinds():
    with open(work.PEAKS_FILE) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
