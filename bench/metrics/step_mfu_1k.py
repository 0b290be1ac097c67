"""step_mfu_1k: ``step_mfu`` in the 1,024-token cell: the operations of
the denoising steps completed for live requests over the traced window
times the chips times the int8 peak (the same reader, scoped to that
cell). The whole step's share that a gain claimed there is bounded by."""

from harness.cell import load_reader


def read(run):
    return load_reader("step_mfu")(run)
