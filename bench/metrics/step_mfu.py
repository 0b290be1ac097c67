"""step_mfu: operations of the denoising steps the traced pumps completed
for live requests (both CFG halves of DiT's published forward), over the
traced window times the chips times the chip's int8 peak. W8A8 runs on
the int8 MXU, so the int8 peak is the one divided by."""

from harness import ops


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or not t["steps"]:
        return None
    work = t["steps"] * ops.slot_step_ops(run.config)
    return 100.0 * work / (t["window_s"] * run.chips
                           * run.peaks["int8_ops"])
