"""The 1,024-token cell's readers on a synthetic reduced trace."""
import json
import os

import pytest

from harness import cell, ops, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with(trace):
    with open(os.path.join(BENCH, "configs",
                           "dit-xl2-512-w8a8-cal1.json")) as f:
        c = json.load(f)
    return cell.Run(cell={"name": "xl2-512-w8a8.saturated", "chips": 1},
                    config=c, traffic={"loop": "closed"}, seconds=30.0,
                    seed=1, chips=1, device_kind="TPU v5 lite",
                    peaks=peaks.peaks("TPU v5 lite"), trace=trace)


TRACE = {"window_s": 12.0, "busy_s": 10.0, "steps": 40,
         "kinds": {"flash_attn_mrq_vec": (56, 4.0),
                   "int8_matmul_fq_vec": (300, 3.0)}}


def test_attention_share_of_busy_time():
    assert cell.load_reader("attn_share_1k")(run_with(TRACE)) == \
        pytest.approx(40.0)


def test_roofline_and_mfu_are_the_accepted_formulas_at_1k_tokens():
    run = run_with(TRACE)
    c, pk = run.config, run.peaks
    assert run.rows == 4 and ops.dims(c)["tokens"] == 1024
    ideal = ops.roofline_s(ops.attention_ops(c, 4),
                           ops.attention_bytes(c, 4, 2),
                           pk["int8_ops"], pk["hbm_bytes_per_s"])
    assert cell.load_reader("attn_roofline_1k")(run) == \
        pytest.approx(100.0 * 56 * ideal / 4.0)
    mfu = 100.0 * 40 * ops.slot_step_ops(c) / (12.0 * pk["int8_ops"])
    assert cell.load_reader("step_mfu_1k")(run) == pytest.approx(mfu)


@pytest.mark.parametrize("name", ["attn_share_1k", "attn_roofline_1k",
                                  "step_mfu_1k"])
def test_nothing_to_read_gives_nothing(name):
    assert cell.load_reader(name)(run_with(None)) is None
    assert cell.load_reader(name)(run_with(
        dict(TRACE, kinds={}, steps=0))) is None
