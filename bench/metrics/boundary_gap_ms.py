"""boundary_gap_ms: device idle time (gaps of 20 us or more) that falls
inside the benchmark's host spans around ``AsyncServeEngine.pump``,
divided by the traced pumps: what each chunk boundary costs the device."""


def read(run):
    t = run.trace
    if not t or not t["n_pumps"]:
        return None
    return 1000.0 * t["idle_by_host_s"].get("bench.pump", 0.0) / t["n_pumps"]
