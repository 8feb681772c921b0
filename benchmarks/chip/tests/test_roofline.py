"""The ADC stage's work counted by hand on a small layout, and the peaks
table."""
import json
import os
import types

import numpy as np
import pytest

from harness.spec import HERE, load_module

adc = load_module(os.path.join(HERE, "roofline", "adc.py"))


def layout():
    # 3 lists, blocks of 4 slots: list 0 owns rows 0-1 (4 + 2 live),
    # list 1 row 2 (3 live), list 2 row 3 (1 live); row 4 is the pad block
    slots = np.full((5, 4), -1)
    slots[0] = [0, 1, 2, 3]
    slots[1, :2] = [4, 5]
    slots[2, :3] = [6, 7, 8]
    slots[3, :1] = [9]
    return types.SimpleNamespace(C=3, slots=slots,
                                 block_cluster=np.array([0, 0, 1, 2, -1]))


def test_list_rows():
    assert adc.list_rows(layout()).tolist() == [6, 3, 1]


def test_count_by_hand():
    idx = types.SimpleNamespace(layout=layout(), centroids=np.eye(3, 8),
                                m=4, ksub=16, nprobe=2)
    q = np.zeros((2, 8))
    q[0, :3] = [1.0, 0.5, 0.1]   # probes lists 0 and 1: 6 + 3 rows
    q[1, :3] = [0.1, 0.2, 1.0]   # probes lists 2 and 1: 1 + 3 rows
    run = types.SimpleNamespace(db=types.SimpleNamespace(index=idx),
                                queries=q)
    got = adc.count(run)
    rows = 9 + 4
    assert got == {"rows": rows, "ops": rows * 4,
                   "bytes": rows * (4 + 4) + 2 * 4 * 16 * 4, "queries": 2}


def test_count_needs_an_ivf_layout():
    run = types.SimpleNamespace(db=types.SimpleNamespace(index=object()),
                                queries=np.zeros((1, 4)))
    assert adc.count(run) is None


def test_peaks_table():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flop_per_s"] == 197e12 and v5e["int8_op_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in peaks["source"]
    with pytest.raises(KeyError):
        peaks["an unknown device"]
