"""Sharded batched serving subsystem: request coalescing, CFG-paired
batching (bit-identical to separate forwards), per-request-keyed sampler
(batch-composition invariance — the property that makes padding and
sharding safe), engine end-to-end fp + fused-int8, multi-device
shard_map identity (subprocess), and the modeled throughput floor."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.diffusion import DiffusionCfg, ddpm_sample_paired, make_schedule
from repro.launch.mesh import make_debug_mesh
from repro.models import dit_apply
from repro.quant import QuantRecipe, quantize
from repro.serving import (
    GenRequest, RequestScheduler, ServeEngine, bucket_steps, coalesce,
)

DIF = DiffusionCfg(T=40, tgq_groups=4)


# ---------------------------------------------------------------------------
# batching / scheduling (pure)
# ---------------------------------------------------------------------------
def test_bucket_steps():
    assert bucket_steps(10, (25, 50, 100)) == 25
    assert bucket_steps(25, (25, 50, 100)) == 25
    assert bucket_steps(26, (25, 50, 100)) == 50
    assert bucket_steps(999, (25, 50, 100)) == 100


def test_coalesce_shapes_padding_and_coverage():
    reqs = [GenRequest(request_id=i, label=i, steps=s, cfg_scale=1.0, seed=i)
            for i, s in enumerate([20, 20, 20, 40, 40])]
    mbs = coalesce(reqs, batch=2, step_buckets=(25, 50))
    assert [mb.steps for mb in mbs] == [25, 25, 50]
    assert all(mb.batch == 2 for mb in mbs)
    # padding only on the trailing partial batch of each bucket
    assert [mb.n_padded for mb in mbs] == [0, 1, 0]
    served = [rid for mb in mbs for rid in mb.request_ids]
    assert sorted(served) == [0, 1, 2, 3, 4]
    # padded slots are marked invalid and carry benign params
    tail = mbs[1]
    assert tail.valid.tolist() == [True, False]
    assert tail.guidance[1] == 1.0


def test_scheduler_submit_all_keeps_ids_unique():
    """Engine results are keyed by request id — submit() after
    submit_all() must never mint a duplicate."""
    sch = RequestScheduler(microbatch=2, step_buckets=(25,))
    sch.submit_all([GenRequest(request_id=0, label=1, steps=25),
                    GenRequest(request_id=7, label=2, steps=25)])
    rid = sch.submit(label=3, steps=25)
    assert rid == 8
    ids = [r.request_id for r in sch.pending]
    assert len(ids) == len(set(ids))
    with pytest.raises(ValueError, match="duplicate request ids"):
        sch.submit_all([GenRequest(request_id=7, label=0, steps=25)])
    assert len(sch.pending) == 3                  # rejected batch not queued


def test_scheduler_run_validates_before_draining(tiny_dit):
    """A scheduler/engine config mismatch must raise BEFORE the queue is
    flushed — pending requests survive for a corrected retry."""
    cfg, p = tiny_dit
    eng = ServeEngine(p, cfg, DIF, microbatch=2, step_buckets=(4,))
    sch = RequestScheduler(microbatch=4, step_buckets=(4,))
    sch.submit(label=1, steps=4)
    with pytest.raises(ValueError, match="microbatch"):
        sch.run(eng)
    assert len(sch.pending) == 1
    sch2 = RequestScheduler(microbatch=2, step_buckets=(4, 8))
    sch2.submit(label=1, steps=8)
    with pytest.raises(ValueError, match="buckets"):
        sch2.run(eng)
    assert len(sch2.pending) == 1


def test_scheduler_partial_flush_policy():
    sch = RequestScheduler(microbatch=4, step_buckets=(25,))
    for i in range(6):
        sch.submit(label=i, steps=25)
    full = sch.flush(partial=False)           # only the full batch leaves
    assert len(full) == 1 and full[0].n_padded == 0
    assert len(sch.pending) == 2              # remainder stays queued
    drained = sch.flush(partial=True)
    assert len(drained) == 1 and drained[0].n_padded == 2
    assert sch.pending == []


# ---------------------------------------------------------------------------
# CFG pairing: one 2B forward == two separate forwards, bit for bit
# ---------------------------------------------------------------------------
def test_cfg_paired_forward_bit_identical(tiny_dit):
    cfg, p = tiny_dit
    key = jax.random.PRNGKey(5)
    B = 3
    x = jax.random.normal(key, (B, cfg.img_size, cfg.img_size, cfg.in_ch))
    t = jnp.full((B,), 7, jnp.int32)
    y = jnp.arange(B, dtype=jnp.int32)
    null = jnp.full((B,), cfg.n_classes, jnp.int32)

    paired = dit_apply(p, cfg, jnp.concatenate([x, x]),
                       jnp.concatenate([t, t]), jnp.concatenate([y, null]))
    eps_c, eps_u = jnp.split(paired, 2)
    np.testing.assert_array_equal(np.asarray(eps_c),
                                  np.asarray(dit_apply(p, cfg, x, t, y)))
    np.testing.assert_array_equal(np.asarray(eps_u),
                                  np.asarray(dit_apply(p, cfg, x, t, null)))


# ---------------------------------------------------------------------------
# per-request keys: a sample depends only on its own request
# ---------------------------------------------------------------------------
def _eps(p, cfg):
    return lambda x, t, y, c: dit_apply(p, cfg, x, t, y, ctx=c)


def test_paired_sampler_batch_invariant(tiny_dit):
    cfg, p = tiny_dit
    sched = make_schedule(DIF)
    shape3 = (3, cfg.img_size, cfg.img_size, cfg.in_ch)
    y = jnp.asarray([1, 4, 2], jnp.int32)
    seeds = jnp.asarray([11, 12, 13], jnp.uint32)
    gsc = jnp.asarray([1.0, 1.5, 0.0], jnp.float32)
    batched = ddpm_sample_paired(_eps(p, cfg), DIF, sched, shape3, y, seeds,
                                 gsc, null_label=cfg.n_classes, steps=4)
    for i in range(3):
        alone = ddpm_sample_paired(
            _eps(p, cfg), DIF, sched, (1,) + shape3[1:], y[i:i + 1],
            seeds[i:i + 1], gsc[i:i + 1], null_label=cfg.n_classes, steps=4)
        np.testing.assert_array_equal(np.asarray(batched[i]),
                                      np.asarray(alone[0]))


def test_guidance_one_matches_conditional_sampling(tiny_dit):
    """s=1 must reduce to eps_c: eps_u + 1*(eps_c - eps_u)."""
    cfg, p = tiny_dit
    sched = make_schedule(DIF)
    shape = (2, cfg.img_size, cfg.img_size, cfg.in_ch)
    y = jnp.asarray([3, 0], jnp.int32)
    out = ddpm_sample_paired(
        _eps(p, cfg), DIF, sched, shape, y, jnp.asarray([7, 8], jnp.uint32),
        jnp.ones((2,), jnp.float32), null_label=cfg.n_classes, steps=4)
    assert bool(jnp.all(jnp.isfinite(out)))
    # and s=0 is unconditional: labels must not matter
    out0a = ddpm_sample_paired(
        _eps(p, cfg), DIF, sched, shape, y, jnp.asarray([7, 8], jnp.uint32),
        jnp.zeros((2,), jnp.float32), null_label=cfg.n_classes, steps=4)
    out0b = ddpm_sample_paired(
        _eps(p, cfg), DIF, sched, shape, 1 - y,
        jnp.asarray([7, 8], jnp.uint32), jnp.zeros((2,), jnp.float32),
        null_label=cfg.n_classes, steps=4)
    np.testing.assert_allclose(np.asarray(out0a), np.asarray(out0b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------
def _mesh11():
    return make_debug_mesh(1, 1)


def test_engine_fp_end_to_end(tiny_dit):
    cfg, p = tiny_dit
    sched = make_schedule(DIF)
    eng = ServeEngine(p, cfg, DIF, sched, mesh=_mesh11(), microbatch=2,
                      step_buckets=(4, 8))
    reqs = [GenRequest(request_id=i, label=i % cfg.n_classes, steps=s,
                       cfg_scale=1.5, seed=50 + i)
            for i, s in enumerate([4, 4, 4, 8, 8])]
    res = eng.serve(reqs)
    assert sorted(res) == [0, 1, 2, 3, 4]
    assert res[0].steps == 4 and res[3].steps == 8
    # one compile per step bucket, padding only on the two bucket tails
    assert sorted(eng.stats["compiled_buckets"]) == [4, 8]
    assert eng.stats["microbatches"] == 3
    assert eng.stats["padded_slots"] == 1
    # engine result == calling the paired sampler directly
    direct = ddpm_sample_paired(
        _eps(p, cfg), DIF, sched, (2, cfg.img_size, cfg.img_size, cfg.in_ch),
        jnp.asarray([0, 1], jnp.int32), jnp.asarray([50, 51], jnp.uint32),
        jnp.full((2,), 1.5, jnp.float32), null_label=cfg.n_classes, steps=4)
    np.testing.assert_array_equal(res[0].sample, np.asarray(direct[0]))
    np.testing.assert_array_equal(res[1].sample, np.asarray(direct[1]))


def test_engine_microbatch_validation(tiny_dit):
    cfg, p = tiny_dit
    eng = ServeEngine(p, cfg, DIF, microbatch=2, step_buckets=(4,))
    with pytest.raises(ValueError, match="slots"):
        eng.run_microbatch(coalesce([GenRequest(0, 0, 4)], 4, (4,))[0])
    with pytest.raises(ValueError, match="buckets"):
        eng.run_microbatch(coalesce([GenRequest(0, 0, 8)], 2, (8,))[0])
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(p, cfg, DIF, mesh=_fake_mesh4(), microbatch=3,
                    step_buckets=(4,))


def _fake_mesh4():
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (4, 1)
    return FakeMesh()


def test_engine_w8a8_kernel_path(tiny_dit, monkeypatch):
    """Quantized serving through the engine: TGQ-packed fused int8 kernels
    fire under the shard_map'd scan, samples are finite, and mesh vs
    no-mesh execution is bit-identical."""
    from repro.kernels import ops as kops

    cfg, p = tiny_dit
    sched = make_schedule(DIF)
    art = quantize(p, cfg, DIF,
                   QuantRecipe(bits="w8a8", method="range", n_per_group=1,
                               calib_batch=1), sched=sched)
    qp2 = art.qparams
    n_pack = sum(1 for v in qp2.values() if "int8" in v or "int8_mrq" in v)
    assert n_pack >= 5, "range calibration must pack the DiT linears"
    assert any(v.get("int8", {}).get("groups") == DIF.tgq_groups
               for v in qp2.values()), "packs must be time-grouped"
    ctx = art.context()
    assert ctx.kernel, "w8a8 artifact must default to the kernel path"

    calls = []
    orig = kops.int8_matmul_fq
    monkeypatch.setattr(kops, "int8_matmul_fq",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])

    reqs = [GenRequest(request_id=i, label=i % cfg.n_classes, steps=4,
                       cfg_scale=1.5, seed=90 + i) for i in range(2)]
    eng = ServeEngine(p, cfg, DIF, sched, ctx=ctx, mesh=_mesh11(),
                      microbatch=2, step_buckets=(4,))
    res = eng.serve(reqs)
    assert len(calls) >= 1, "fused int8 kernel was not traced"
    s = np.stack([res[i].sample for i in range(2)])
    assert np.isfinite(s).all()

    eng_nomesh = ServeEngine(p, cfg, DIF, sched, ctx=ctx, microbatch=2,
                             step_buckets=(4,))
    res2 = eng_nomesh.serve(reqs)
    for i in range(2):
        np.testing.assert_array_equal(res[i].sample, res2[i].sample)


# ---------------------------------------------------------------------------
# multi-device: sharded w8a8 == single-device w8a8 (subprocess; this test
# process is pinned to 1 CPU device by conftest)
# ---------------------------------------------------------------------------
_SHARDED_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 2, jax.device_count()
from repro.diffusion import DiffusionCfg, make_schedule
from repro.launch.mesh import make_serving_mesh
from repro.models import DiTCfg, dit_init
from repro.quant import QuantRecipe, quantize
from repro.serving import GenRequest, ServeEngine

cfg = DiTCfg(img_size=8, in_ch=4, patch=2, d_model=32, n_layers=2,
             n_heads=4, n_classes=8)
p = dit_init(jax.random.PRNGKey(0), cfg)
p = jax.tree.map(
    lambda a: a + jax.random.normal(jax.random.PRNGKey(1), a.shape) * 0.01, p)
dif = DiffusionCfg(T=40, tgq_groups=4)
sched = make_schedule(dif)
art = quantize(p, cfg, dif, QuantRecipe(bits="w8a8", method="range",
                                        n_per_group=1, calib_batch=1),
               sched=sched)
reqs = [GenRequest(request_id=i, label=i % 8, steps=4, cfg_scale=1.5,
                   seed=300 + i) for i in range(4)]
out = {}
for nd in (2, 1):
    mesh = make_serving_mesh(nd)
    eng = ServeEngine.from_artifact(p, art, sched=sched, mesh=mesh,
                                    microbatch=4, step_buckets=(4,))
    out[nd] = eng.serve(reqs)
ok = all(np.array_equal(out[2][i].sample, out[1][i].sample)
         for i in range(4))
print("IDENTICAL" if ok else "MISMATCH")
"""


def test_sharded_w8a8_identical_to_single_device():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "IDENTICAL" in r.stdout, (r.stdout, r.stderr[-2000:])


# ---------------------------------------------------------------------------
# modeled serving throughput floor (acceptance: >=1.5x at batch == n_dev)
# ---------------------------------------------------------------------------
def test_modeled_throughput_floor():
    from benchmarks.serve_throughput import XL2, modeled_requests_per_sec
    for n_dev in (4, 8):
        fp = modeled_requests_per_sec(XL2, n_dev, n_dev, 100, "fp")
        q8 = modeled_requests_per_sec(XL2, n_dev, n_dev, 100, "int8")
        qc = modeled_requests_per_sec(XL2, n_dev, n_dev, 100,
                                      "int8_composed")
        assert q8["req_per_s"] / fp["req_per_s"] >= 1.5
        # flash attention (the serving default) removes the modeled (S,S)
        # scores/codes round-trip — the honest end-to-end ratio must beat
        # the composed three-kernel path's ~1.9x
        assert qc["req_per_s"] / fp["req_per_s"] >= 1.5
        assert q8["req_per_s"] > qc["req_per_s"]
        assert q8["req_per_s"] / fp["req_per_s"] >= 1.9


# ---------------------------------------------------------------------------
# persistent compilation cache location
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"],
                         ids=["checkout", "env"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The serving entry points keep JAX's compile cache where
    JAX_COMPILATION_CACHE_DIR says (setting nothing over it), and
    otherwise in one fixed, git-ignored directory of the checkout."""
    from repro.launch import compile_cache as cc
    if env_dir is None:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(cc.ENV_VAR, env_dir)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = cc.enable_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    if env_dir is not None:
        assert got == env_dir and now == prev
        return
    root = os.path.join(os.path.dirname(__file__), "..")
    assert got == now == os.path.join(os.path.realpath(root), ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
