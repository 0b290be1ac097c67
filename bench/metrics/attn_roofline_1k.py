"""attn_roofline_1k: ``attn_roofline`` in the 1,024-token cell: the flash
MRQ attention calls against their roofline at the cell's rows and
tokens (the same reader, scoped to that cell)."""

from harness.cell import load_reader


def read(run):
    return load_reader("attn_roofline")(run)
