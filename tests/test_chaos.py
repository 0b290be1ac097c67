"""Chaos suite: the async engine under injected faults
(`repro.serving.faults`). Every fault is deterministic, every outcome
structured, and the headline invariant holds throughout: UNINJECTED
requests complete bit-identical to the synchronous step-bucketed path no
matter what happens to their neighbours.

Covers: NaN-burst quarantine + retry determinism (fp AND w8a8 kernel
contexts — the `fold_in(PRNGKey(seed), step)` per-slot key contract),
sticky poison -> bounded retries -> structured FAILED, the graceful-
degradation ladder (flash attn -> composed -> fake-quant) on dispatch
faults, ladder exhaustion -> EngineFault with every live request failed,
deadline overruns driven by a FakeClock (no sleeping), and artifact
corruption surfacing as a fail-fast shard-naming error at load.

The dispatch-ahead pipeline section re-runs the NaN / deadline / ladder
faults with pipeline depth 1 vs 2 (the engine speculates the next chunk
before reading back the current one, and must drain the in-flight
dispatch at every fault/lifecycle boundary): outcomes, retry counts,
degradation logs, and samples are asserted byte-for-byte equal across
depths, and a subprocess test repeats the quarantine contract on a
2-device sharded slot pool."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.diffusion import DiffusionCfg
from repro.quant import QuantArtifact, QuantRecipe, quantize
from repro.serving import (
    AsyncServeEngine, EngineFault, FakeClock, Fault, FaultInjector,
    GenRequest, ServeEngine,
)

DIF = DiffusionCfg(T=40, tgq_groups=4)
BUCKETS = (4, 6)

REQS = [
    GenRequest(request_id=0, label=1, steps=4, cfg_scale=1.5, seed=10),
    GenRequest(request_id=1, label=2, steps=6, cfg_scale=1.0, seed=11),
    GenRequest(request_id=2, label=3, steps=4, cfg_scale=0.0, seed=12),
]


@pytest.fixture(scope="module")
def sync_ref(tiny_dit):
    cfg, p = tiny_dit
    eng = ServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS)
    return eng.serve(REQS)


@pytest.fixture(scope="module")
def w8a8(tiny_dit):
    cfg, p = tiny_dit
    return quantize(p, cfg, DIF, QuantRecipe(bits="w8a8", method="range",
                                             n_per_group=1, calib_batch=1))


# ---------------------------------------------------------------------------
# NaN quarantine + retry determinism
# ---------------------------------------------------------------------------
def test_nan_burst_retry_is_bit_identical_fp(tiny_dit, sync_ref):
    """A NaN burst poisons request 1 mid-chain; the engine quarantines
    ONLY that slot and retries it with the same fold_in(PRNGKey(seed), i)
    keys — the retried sample, and every neighbour, is bit-identical to
    the uninjected synchronous run."""
    cfg, p = tiny_dit
    inj = FaultInjector([Fault(kind="nan", request_id=1, at_step=2)])
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS,
                           chunk=2, max_retries=2, injector=inj)
    out = eng.serve(REQS)
    assert all(o.status == "OK" for o in out.values())
    assert out[1].retries == 1 and out[0].retries == 0
    for rid, o in out.items():
        assert np.array_equal(o.sample, sync_ref[rid].sample), rid
    assert len(inj.fired) == 1 and eng.stats["retries"] == 1


def test_nan_burst_retry_is_bit_identical_w8a8(tiny_dit, w8a8):
    """Same retry-determinism contract through the fused int8 kernels."""
    cfg, p = tiny_dit
    sync = ServeEngine.from_artifact(p, w8a8, microbatch=2,
                                     step_buckets=BUCKETS)
    ref = sync.serve(REQS)
    inj = FaultInjector([Fault(kind="nan", request_id=2, at_step=1)])
    eng = AsyncServeEngine.from_artifact(p, w8a8, microbatch=2,
                                         step_buckets=BUCKETS, chunk=3,
                                         injector=inj)
    out = eng.serve(REQS)
    assert all(o.status == "OK" for o in out.values())
    assert out[2].retries == 1
    for rid, o in out.items():
        assert np.array_equal(o.sample, ref[rid].sample), rid


def test_sticky_poison_fails_structured_after_max_retries(tiny_dit,
                                                          sync_ref):
    cfg, p = tiny_dit
    inj = FaultInjector([Fault(kind="nan", request_id=0, at_step=1,
                               sticky=True)])
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS,
                           chunk=2, max_retries=2, injector=inj)
    out = eng.serve(REQS)
    o = out[0]
    assert o.status == "FAILED" and o.sample is None
    assert o.error.code == "nan_poisoned" and o.error.retries == 2
    assert "request 0" in o.error.message
    # the quarantine is per-slot: neighbours finish bit-identical
    for rid in (1, 2):
        assert out[rid].status == "OK"
        assert np.array_equal(out[rid].sample, sync_ref[rid].sample)


def test_slot_error_fault_kind(tiny_dit):
    cfg, p = tiny_dit
    inj = FaultInjector([Fault(kind="slot_error", request_id=0, at_step=0,
                               sticky=True)])
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS,
                           chunk=2, max_retries=1, injector=inj)
    out = eng.serve(REQS[:2])
    assert out[0].status == "FAILED" and out[0].error.code == "slot_error"
    assert out[1].status == "OK"


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------
def test_dispatch_faults_walk_the_degradation_ladder(tiny_dit, w8a8):
    """Two dispatch faults walk flash -> composed -> fake-quant; each rung
    is logged with a reason and every request still completes OK."""
    cfg, p = tiny_dit
    inj = FaultInjector([Fault(kind="dispatch_error", at_dispatch=1),
                         Fault(kind="dispatch_error", at_dispatch=2)])
    eng = AsyncServeEngine.from_artifact(p, w8a8, microbatch=2,
                                         step_buckets=BUCKETS, chunk=2,
                                         injector=inj)
    assert eng.ctx.kernel and eng.ctx.attn_impl == "flash"
    out = eng.serve(REQS)
    assert all(o.status == "OK" for o in out.values())
    reasons = [d["reason"] for d in eng.stats["degradations"]]
    assert len(reasons) == 2
    assert "composed" in reasons[0] and "fake-quant" in reasons[1]
    assert eng.ctx.kernel is False            # landed on the bottom rung


def test_ladder_exhausted_fails_everything_structured(tiny_dit):
    """An fp context has no rung below it: a dispatch fault fails every
    live request with a structured engine_fault and raises EngineFault —
    loud, attributable, nothing dropped on the floor."""
    cfg, p = tiny_dit
    inj = FaultInjector([Fault(kind="dispatch_error", at_dispatch=1)])
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS,
                           chunk=2, injector=inj)
    for r in REQS:
        eng.submit_request(r)
    with pytest.raises(EngineFault, match="no degradation rung"):
        eng.run_until_drained()
    assert len(eng.outcomes) == len(REQS)
    assert all(o.status == "FAILED" and o.error.code == "engine_fault"
               for o in eng.outcomes.values())


def test_chunk_build_fault_raises_past_the_ladder(tiny_dit, w8a8):
    """A chunk executable that fails to trace or compile is a build fault,
    not a dispatch fault: it raises out of the first pump instead of
    stepping down to a slower rung, and nothing is logged as degraded."""
    cfg, p = tiny_dit

    class Unbuildable(type(w8a8.context(kernel=True))):
        def attention(self, name, q, k, v, *, mask=None, scale=1.0):
            raise NotImplementedError("kernel refused by the compiler")

    eng = AsyncServeEngine.from_artifact(p, w8a8, microbatch=2,
                                         step_buckets=BUCKETS, chunk=2)
    eng.ctx = Unbuildable(**{f.name: getattr(eng.ctx, f.name)
                             for f in dataclasses.fields(eng.ctx)})
    eng._chunk_fn = eng._build_chunk()
    eng.submit_request(REQS[0])
    with pytest.raises(NotImplementedError, match="refused"):
        eng.pump()
    assert eng.stats["degradations"] == []
    assert eng.ctx.kernel and eng.ctx.attn_impl == "flash"


# ---------------------------------------------------------------------------
# deadlines (FakeClock: no sleeping)
# ---------------------------------------------------------------------------
def test_deadline_overrun_cancels_at_chunk_boundary(tiny_dit, sync_ref):
    cfg, p = tiny_dit
    clk = FakeClock()
    inj = FaultInjector([Fault(kind="stall", at_dispatch=2, seconds=100.0)],
                        clock=clk)
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=2, step_buckets=BUCKETS,
                           chunk=2, deadline_s=10.0, clock=clk, injector=inj)
    out = eng.serve(REQS)
    cancelled = [o for o in out.values() if o.status == "CANCELLED"]
    assert cancelled and all(o.error.code == "deadline" for o in cancelled)
    # request 0 (4 steps, chunk 2) finished BY the stalled boundary: a
    # request that completes on time delivers OK even if the deadline has
    # since passed
    assert out[0].status == "OK"
    assert np.array_equal(out[0].sample, sync_ref[0].sample)


def test_deadline_expired_in_queue_never_admitted(tiny_dit):
    cfg, p = tiny_dit
    clk = FakeClock()
    eng = AsyncServeEngine(p, cfg, DIF, microbatch=1, step_buckets=BUCKETS,
                           clock=clk)
    rid = eng.submit(label=1, steps=4, deadline_s=5.0)
    clk.advance(50.0)                        # expires while queued
    out = eng.run_until_drained()
    assert out[rid].status == "CANCELLED"
    assert out[rid].error.code == "deadline"
    assert eng.stats["admitted"] == 0        # never wasted a slot


# ---------------------------------------------------------------------------
# dispatch-ahead pipelining: faults at chunk boundaries with a two-deep
# in-flight dispatch, and the 2-device sharded slot pool (subprocess)
# ---------------------------------------------------------------------------
def test_pipeline_nan_quarantine_matches_unpipelined(tiny_dit, sync_ref):
    """pipeline=2 speculates the next chunk before the current one is read
    back; a NaN quarantine resets the slot, so the stale in-flight
    dispatch must be drained. Outcomes, retry counts, and samples are
    byte-for-byte those of the unpipelined engine (and of the uninjected
    sync run)."""
    cfg, p = tiny_dit
    outs = {}
    for depth in (1, 2):
        inj = FaultInjector([Fault(kind="nan", request_id=1, at_step=2)])
        eng = AsyncServeEngine(p, cfg, DIF, microbatch=2,
                               step_buckets=BUCKETS, chunk=2, max_retries=2,
                               pipeline=depth, injector=inj)
        outs[depth] = eng.serve(REQS)
    for rid in outs[1]:
        a, b = outs[1][rid], outs[2][rid]
        assert a.status == b.status == "OK"
        assert a.retries == b.retries
        assert np.array_equal(a.sample, b.sample)
        assert np.array_equal(b.sample, sync_ref[rid].sample), rid


def test_pipeline_deadline_cancel_matches_unpipelined(tiny_dit, sync_ref):
    """Deadline cancellation happens at a chunk boundary while a
    speculative chunk is in flight — the cancel must drain it, and the
    set of OK/CANCELLED outcomes must match pipeline=1 exactly."""
    cfg, p = tiny_dit
    outs = {}
    for depth in (1, 2):
        clk = FakeClock()
        inj = FaultInjector([Fault(kind="stall", at_dispatch=2,
                                   seconds=100.0)], clock=clk)
        eng = AsyncServeEngine(p, cfg, DIF, microbatch=2,
                               step_buckets=BUCKETS, chunk=2,
                               deadline_s=10.0, clock=clk, pipeline=depth,
                               injector=inj)
        outs[depth] = eng.serve(REQS)
    for rid in outs[1]:
        a, b = outs[1][rid], outs[2][rid]
        assert a.status == b.status
        if a.status == "OK":
            assert np.array_equal(a.sample, b.sample), rid
        else:
            assert b.error.code == "deadline"
    assert outs[2][0].status == "OK"
    assert np.array_equal(outs[2][0].sample, sync_ref[0].sample)


def test_pipeline_degradation_ladder_matches_unpipelined(tiny_dit, w8a8):
    """Dispatch faults fire while a speculative chunk is in flight: the
    ladder drains the pipeline, degrades, rebuilds the executable, and
    re-dispatches from committed slot state — same rungs, same reasons,
    same samples as pipeline=1 (a failed dispatch stays side-effect
    free at any depth)."""
    cfg, p = tiny_dit
    outs, reasons = {}, {}
    for depth in (1, 2):
        inj = FaultInjector([Fault(kind="dispatch_error", at_dispatch=1),
                             Fault(kind="dispatch_error", at_dispatch=2)])
        eng = AsyncServeEngine.from_artifact(p, w8a8, microbatch=2,
                                             step_buckets=BUCKETS, chunk=2,
                                             pipeline=depth, injector=inj)
        outs[depth] = eng.serve(REQS)
        reasons[depth] = [d["reason"] for d in eng.stats["degradations"]]
        assert eng.ctx.kernel is False
    assert reasons[1] == reasons[2] and len(reasons[2]) == 2
    for rid in outs[1]:
        assert outs[1][rid].status == outs[2][rid].status == "OK"
        assert np.array_equal(outs[1][rid].sample, outs[2][rid].sample), rid


_PIPELINE_DP_SCRIPT = r"""
import jax, numpy as np
assert jax.device_count() == 2, jax.device_count()
from repro.diffusion import DiffusionCfg
from repro.launch.mesh import make_serving_mesh
from repro.models import DiTCfg, dit_init
from repro.serving import (AsyncServeEngine, Fault, FaultInjector,
                           GenRequest, ServeEngine)

cfg = DiTCfg(img_size=8, in_ch=4, patch=2, d_model=32, n_layers=2,
             n_heads=4, n_classes=8)
p = dit_init(jax.random.PRNGKey(0), cfg)
dif = DiffusionCfg(T=40, tgq_groups=4)
reqs = [GenRequest(request_id=i, label=i % 8, steps=s, cfg_scale=1.5,
                   seed=700 + i) for i, s in enumerate([4, 6, 4, 6])]
sync = ServeEngine(p, cfg, dif, microbatch=2,
                   step_buckets=(4, 6)).serve(reqs)
inj = FaultInjector([Fault(kind="nan", request_id=1, at_step=2)])
eng = AsyncServeEngine(p, cfg, dif, mesh=make_serving_mesh(), microbatch=4,
                       step_buckets=(4, 6), chunk=2, pipeline=2,
                       max_retries=2, injector=inj)
out = eng.serve(reqs)
ok = (all(o.status == "OK" for o in out.values())
      and out[1].retries == 1
      and all(np.array_equal(out[i].sample, sync[i].sample)
              for i in range(4)))
print("IDENTICAL" if ok else "MISMATCH")
"""


def test_pipeline_nan_quarantine_on_2dev_sharded_pool():
    """The headline chaos invariant on the scaled-out engine: a 2-device
    sharded slot pool with a two-deep dispatch pipeline quarantines one
    poisoned slot and still delivers every sample bit-identical to the
    single-device synchronous path (subprocess: this test process is
    pinned to 1 CPU device by conftest)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _PIPELINE_DP_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "IDENTICAL" in r.stdout, (r.stdout, r.stderr[-2000:])


# ---------------------------------------------------------------------------
# artifact corruption
# ---------------------------------------------------------------------------
def test_artifact_byteflip_fails_fast_naming_shard(tiny_dit, w8a8,
                                                   tmp_path):
    """Flip one byte in a saved artifact's npz shard: load must fail fast
    with an error naming the shard file and the leaves it carries —
    not a cryptic zip/zlib traceback, and never silently-wrong
    quantizer state."""
    path = str(tmp_path / "art")
    w8a8.save(path)
    step_dir = os.path.join(path, "step_00000000")
    shard = os.path.join(step_dir, "shard_00000.npz")
    data = bytearray(open(shard, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(shard, "wb").write(bytes(data))
    with pytest.raises(ValueError, match=r"shard_00000\.npz is corrupted"):
        QuantArtifact.load(path)
    with pytest.raises(ValueError, match="leaf 0"):
        ckpt.verify_shards(path)


def test_artifact_truncated_shard(tiny_dit, w8a8, tmp_path):
    path = str(tmp_path / "art")
    w8a8.save(path)
    shard = os.path.join(path, "step_00000000", "shard_00000.npz")
    data = open(shard, "rb").read()
    open(shard, "wb").write(data[:len(data) // 2])
    with pytest.raises(ValueError, match="corrupted"):
        QuantArtifact.load(path)


def test_artifact_missing_shard(tiny_dit, w8a8, tmp_path):
    path = str(tmp_path / "art")
    w8a8.save(path)
    os.remove(os.path.join(path, "step_00000000", "shard_00000.npz"))
    with pytest.raises(FileNotFoundError, match="missing"):
        QuantArtifact.load(path)


def test_intact_artifact_still_roundtrips(tiny_dit, w8a8, tmp_path):
    """The integrity check must not reject healthy artifacts."""
    path = str(tmp_path / "art")
    w8a8.save(path)
    art = QuantArtifact.load(path)
    assert art.recipe == w8a8.recipe


# ---------------------------------------------------------------------------
# slow sweep: random-but-seeded fault schedules, invariant checked
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_fault_schedule_sweep(tiny_dit, sync_ref):
    """Many seeded fault schedules; invariants: every request terminal,
    every OK sample bit-identical to the uninjected sync run, every
    non-OK outcome carries a structured error."""
    cfg, p = tiny_dit
    rng = np.random.default_rng(0)
    for trial in range(10):
        faults = []
        for rid in range(len(REQS)):
            if rng.random() < 0.5:
                faults.append(Fault(
                    kind="nan", request_id=rid,
                    at_step=int(rng.integers(0, 4)),
                    sticky=bool(rng.random() < 0.2)))
        inj = FaultInjector(faults)
        eng = AsyncServeEngine(p, cfg, DIF, microbatch=2,
                               step_buckets=BUCKETS, chunk=2,
                               max_retries=1, injector=inj)
        out = eng.serve(REQS)
        assert len(out) == len(REQS), f"trial {trial} dropped requests"
        for rid, o in out.items():
            if o.status == "OK":
                assert np.array_equal(o.sample, sync_ref[rid].sample), \
                    (trial, rid)
            else:
                assert o.status == "FAILED" and o.error is not None
