"""attn_roofline: flash MRQ attention (``kernels/flash_attn_mrq.py``)
against its roofline: calls in the trace times max(ops / int8 peak,
bytes / HBM bandwidth) of one layer's attention at the pool's rows, over
their device time."""

import re

from harness import ops

KERNELS = r"flash_attn_mrq(_vec)?"


def read(run):
    t = run.trace
    if not t:
        return None
    calls = secs = 0
    for kind, (n, s) in t["kinds"].items():
        if re.fullmatch(KERNELS, kind):
            calls, secs = calls + n, secs + s
    if not calls or secs <= 0:
        return None
    pk = run.peaks
    ideal = ops.roofline_s(ops.attention_ops(run.config, run.rows),
                           ops.attention_bytes(run.config, run.rows,
                                               run.act_bytes),
                           pk["int8_ops"], pk["hbm_bytes_per_s"])
    return 100.0 * calls * ideal / secs
