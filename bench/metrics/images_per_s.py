"""images_per_s: denoising steps completed in the window for requests
that did not fail (ended OK, or still running at the close), divided by
the steps of an image, divided by the window's seconds. A work rate over
the whole window, so lockstep completions do not quantize it."""


def read(run):
    if run.traffic["loop"] != "closed" or run.window_s <= 0:
        return None
    steps = sum(r.steps_window for r in run.window_requests()
                if r.status in ("OK", "RUNNING", "QUEUED"))
    return steps / run.traffic["steps"] / run.window_s
