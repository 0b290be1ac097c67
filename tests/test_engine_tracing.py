"""`AsyncServeEngine`'s tracing: the counters in ``stats`` (``chunk_runs``,
``drained``, ``live_slot_steps``, ``phase_s``) account for every run of
the chunk executable, and the ``engine.*`` profiler spans nest as
documented and count what they name. Tiny fp DiT, a few seconds each."""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.diffusion import DiffusionCfg
from repro.serving import AsyncServeEngine, GenRequest
from repro.serving.engine import PHASES

DIF = DiffusionCfg(T=40, tgq_groups=4)
BUCKETS = (4, 6)
REQS = [GenRequest(request_id=i, label=i % 8, steps=BUCKETS[i % 2],
                   cfg_scale=1.5, seed=20 + i) for i in range(5)]


def _engine(tiny_dit, pipeline):
    cfg, p = tiny_dit
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS,
                           chunk=2, pipeline=pipeline)
    for r in REQS:
        eng.submit_request(r)
    return eng


@pytest.fixture(scope="module")
def compiled(tiny_dit):
    """One compiled chunk executable for the module: its arguments (the
    pool's shapes) do not depend on ``pipeline``."""
    eng = _engine(tiny_dit, 1)
    eng._compile_chunk()
    return eng._chunk_exec


@pytest.mark.parametrize("pipeline", [1, 2])
def test_counters_account_for_every_chunk_run(tiny_dit, compiled, pipeline):
    eng = _engine(tiny_dit, pipeline)
    calls = [0]

    def counted(*args):
        calls[0] += 1
        return compiled(*args)

    eng._chunk_exec = counted
    consumed = 0
    while eng.queue or eng.active:
        consumed += eng.pump()
        s = eng.stats
        pending = int(eng._pending is not None)
        assert s["chunk_runs"] == calls[0]
        assert s["chunk_runs"] == consumed + s["drained"] + pending
        if pipeline == 1:
            assert s["drained"] == 0 and pending == 0
    s = eng.stats
    assert consumed == s["dispatches"]
    if pipeline == 2:
        assert s["drained"] > 0       # admissions and completions drop one
    assert all(o.status == "OK" for o in eng.outcomes.values())
    assert s["live_slot_steps"] == sum(r.steps for r in REQS)
    assert set(s["phase_s"]) == set(PHASES)
    for total, longest in s["phase_s"].values():
        assert total >= longest > 0.0
    assert not {"completed", "failed", "cancelled"} & set(s)


def _host_spans(log_dir):
    """``engine.*`` events of the trace: (name, start, end, stats)."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_engine_spans_nest_and_count(tiny_dit, tmp_path):
    eng = _engine(tiny_dit, pipeline=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    pumps = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        while eng.queue or eng.active:
            eng.pump()
            pumps += 1
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)

    def inside(sp, parents):
        return any(p[1] <= sp[1] and sp[2] <= p[2] for p in parents)

    assert len(by["engine.pump"]) == pumps
    assert len(by["engine.compile"]) == 1
    assert by["engine.wait"]
    for w in by["engine.wait"]:
        d = [p for p in by["engine.dispatch"] if inside(w, [p])]
        assert d and inside(d[0], by["engine.pump"])
    for name in ("engine.admit", "engine.dispatch", "engine.resolve"):
        assert all(inside(sp, by["engine.pump"]) for sp in by[name])
    assert all(inside(sp, by["engine.resolve"]) for sp in by["engine.pull"])
    ok = sorted(rid for rid, o in eng.outcomes.items() if o.status == "OK")
    assert sorted(sp[3]["request_id"] for sp in by["engine.pull"]) == ok
