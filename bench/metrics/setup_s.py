"""setup_s: process start to the start of the measured window: weights,
the quantization artifact (loaded, or made by quantize() on a checkout's
first run), engine construction, compilation and the warm-up turn."""


def read(run):
    return run.setup_s
