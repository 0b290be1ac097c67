"""The §Perf knobs must be semantics-preserving: sharding constraints and
dispatch pins change layouts, never values."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_debug_mesh
from repro.models import ModelCfg, lm_init, lm_apply


@pytest.fixture(scope="module")
def mesh11():
    return make_debug_mesh(1, 1)


def test_attn_sp_preserves_values(mesh11):
    cfg = ModelCfg(name="t", family="dense", n_layers=2, d_model=64,
                   vocab=128, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128)
    p = lm_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    base, _ = lm_apply(p, cfg, toks)
    cfg_sp = dataclasses.replace(cfg, attn_sp=(("data",), "model"))
    with mesh11:
        sp, _ = jax.jit(lambda pp, tt: lm_apply(pp, cfg_sp, tt))(p, toks)
    np.testing.assert_allclose(base, sp, atol=2e-5)


def test_moe_shard_pin_preserves_values(mesh11):
    cfg = ModelCfg(name="m", family="moe", n_layers=2, d_model=64, vocab=128,
                   n_heads=4, n_kv_heads=2, head_dim=16, moe=True,
                   n_experts=8, top_k=2, n_shared=1, d_expert=32, d_ff=0,
                   capacity_factor=8.0)
    p = lm_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    base, _ = lm_apply(p, cfg, toks)
    cfg_pin = dataclasses.replace(cfg, moe_shard=(("data",), "model"))
    with mesh11:
        pin, _ = jax.jit(lambda pp, tt: lm_apply(pp, cfg_pin, tt))(p, toks)
    np.testing.assert_allclose(base, pin, atol=2e-5)


def test_fisher_norm_modes_both_calibrate(tiny_dit):
    """'batch' (default) and 'raw' both produce working quantizers; the
    normalized mode repairs the cross-timestep clipping artifact
    (DESIGN/EXPERIMENTS; here we just assert both run and differ)."""
    from repro.core import (PTQConfig, QuantContext, run_ptq,
                            build_dit_calibration, dit_loss_fn)
    from repro.diffusion import DiffusionCfg, make_schedule
    from repro.models import dit_apply

    cfg, p = tiny_dit
    dif = DiffusionCfg(T=100, tgq_groups=2)
    sched = make_schedule(dif)
    calib = build_dit_calibration(
        p, cfg, dif, sched, lambda n, k: jax.random.normal(k, (n, 8, 8, 4)),
        jax.random.PRNGKey(3), n_per_group=4, batch=4)
    loss = dit_loss_fn(p, cfg)
    outs = {}
    for mode in ("batch", "raw"):
        qp, _ = run_ptq(loss, calib, PTQConfig(
            wbits=6, abits=6, tgq_groups=2, n_alpha=6, rounds=1,
            fisher_norm=mode))
        b = calib[0][0]
        outs[mode] = dit_apply(p, cfg, b["xt"], b["t"], b["y"],
                               ctx=QuantContext(qparams=qp))
        assert bool(jnp.all(jnp.isfinite(outs[mode])))


def test_vocab_parallel_ce_matches_reference():
    from repro.models.lm import ce_loss
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 64)) * 3
    labels = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, 64)
    labels = labels.at[0, :2].set(-1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None].clip(0), -1)[..., 0]
    mask = (labels != -1).astype(jnp.float32)
    want = jnp.sum((lse - ll) * mask) / mask.sum()
    np.testing.assert_allclose(ce_loss(logits, labels), want, rtol=1e-6)
