"""discarded_chunk_share: the share of the chunk executable's runs in the
traced window whose result the engine threw away. With dispatch-ahead
(``pipeline`` 2) the engine enqueues the next chunk before it reads the
boundary, and drops it when the boundary changes a slot (a completion or
an admission); the device still runs it. Runs are counted in the trace
(the executable with the most device time), pumps that dispatched by the
benchmark's books."""


def read(run):
    t = run.trace
    if not t or not t["chunk_runs"]:
        return None
    used = min(t["pumps_dispatching"], t["chunk_runs"])
    return 100.0 * (t["chunk_runs"] - used) / t["chunk_runs"]
