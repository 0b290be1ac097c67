"""linear_roofline: the fused int8 linears (``kernels/int8_fused.py``,
the ``_vec`` family and its MRQ twin) against their roofline: the sum
over calls of max(ops / int8 peak, bytes / HBM bandwidth), over the sum
of their device time in the trace. Calls are counted in the trace; each
is charged the mean roofline time of one forward's linears, from the
published shapes at the pool's rows."""

import re

from harness import ops

KERNELS = r"int8_matmul(_mrq)?_fq(_vec)?"


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
    per_fwd = ops.linear_calls(run.config, run.rows)
    ideal = sum(ops.roofline_s(ops.linear_ops(c),
                               ops.linear_bytes(c, run.rows, run.act_bytes),
                               pk["int8_ops"], pk["hbm_bytes_per_s"])
                for c in per_fwd) / len(per_fwd)
    return 100.0 * calls * ideal / secs
