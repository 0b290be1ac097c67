"""attn_share_1k: the share of the device's busy time in the traced
window that flash MRQ attention (``kernels/flash_attn_mrq.py``, either
form) takes: at 1,024 tokens attention, not the linears, may set the
pace."""

import re

KERNELS = r"flash_attn_mrq(_vec)?"


def read(run):
    t = run.trace
    if not t or t["busy_s"] <= 0:
        return None
    secs = sum(s for kind, (_, s) in t["kinds"].items()
               if re.fullmatch(KERNELS, kind))
    if secs <= 0:
        return None
    return 100.0 * secs / t["busy_s"]
