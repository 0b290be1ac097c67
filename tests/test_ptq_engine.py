"""PTQ engine integration: op discovery, calibration capture, fisher
alignment, HO search, TGQ grouping, and the Table-III ablation ordering
on a tiny DiT."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CalibrationContext, PTQConfig, QuantContext, RecordingContext,
    build_dit_calibration, dit_loss_fn, run_ptq,
)
from repro.core.baselines import SCHEMES
from repro.core.fisher import discover_tap_shapes, make_fisher_fn
from repro.core.quantizers import TGQ
from repro.diffusion import DiffusionCfg, make_schedule
from repro.models import dit_apply


@pytest.fixture(scope="module")
def dit_setup(tiny_dit):
    cfg, p = tiny_dit
    dif = DiffusionCfg(T=100, tgq_groups=4)
    sched = make_schedule(dif)
    x0 = lambda n, k: jax.random.normal(k, (n, 8, 8, 4))
    calib = build_dit_calibration(p, cfg, dif, sched, x0,
                                  jax.random.PRNGKey(3), n_per_group=8,
                                  batch=4)
    return cfg, p, dif, sched, calib


def test_recording_discovers_ops_and_provenance(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    rec = RecordingContext()
    dit_loss_fn(p, cfg)(rec, calib[0][0])
    names = set(rec.registry)
    assert "blk0/qkv" in names and "blk1/fc2" in names
    assert rec.registry["blk0/attn/pv"].a_kind == "post_softmax"
    assert rec.registry["blk0/fc2"].a_kind == "post_gelu"
    assert rec.registry["blk0/attn/qk"].a_kind == "plain"
    assert rec.registry["blk0/attn/pv"].kind == "einsum"


def test_recording_provenance_ignores_reused_ids():
    """Eager forwards free arrays and allocate new ones at the same
    address, so a later tensor can carry a marked tensor's id: the mark
    must classify only the tensor it was made on."""
    rec = RecordingContext()
    probs, q = jnp.ones((2, 2)), jnp.zeros((2, 2))
    rec.act("probs", probs, "post_softmax")
    rec._marks[id(q)] = rec._marks[id(probs)]    # q lands on a marked id
    rec.einsum("qk", "ij,jk->ik", q, q)
    rec.einsum("pv", "ij,jk->ik", probs, q)
    assert rec.registry["qk"].a_kind == "plain"
    assert rec.registry["pv"].a_kind == "post_softmax"


def test_fisher_taps_match_finite_difference(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    loss = dit_loss_fn(p, cfg)
    batch = calib[0][0]
    shapes = discover_tap_shapes(loss, batch)
    fisher = make_fisher_fn(loss, shapes)
    g = fisher(batch)
    name = "blk0/fc1"
    # finite difference on a single tap coordinate
    from repro.core.contexts import TapContext
    taps0 = {n: jnp.zeros(s, d) for n, (s, d) in shapes.items()}
    eps = 1e-3
    idx = (0, 3, 5)
    tp = dict(taps0)
    tp[name] = taps0[name].at[idx].set(eps)
    tm = dict(taps0)
    tm[name] = taps0[name].at[idx].set(-eps)
    lp = float(loss(TapContext(taps=tp), batch))
    lm = float(loss(TapContext(taps=tm), batch))
    fd = (lp - lm) / (2 * eps)
    np.testing.assert_allclose(float(g[name][idx]), fd, rtol=0.05, atol=1e-5)


def test_tgq_params_are_grouped(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    qp, _ = run_ptq(dit_loss_fn(p, cfg), calib,
                    PTQConfig(tgq_groups=4, n_alpha=6, rounds=1))
    pv = qp["blk0/attn/pv"]
    assert isinstance(pv["x"], TGQ)
    assert pv["x"].inner.s1.shape == (4,)


def test_quant_context_skips_unquantized_ops(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    ctx = QuantContext(qparams={})
    b = calib[0][0]
    fp = dit_apply(p, cfg, b["xt"], b["t"], b["y"])
    q = dit_apply(p, cfg, b["xt"], b["t"], b["y"], ctx=ctx)
    np.testing.assert_allclose(fp, q, atol=1e-6)


@pytest.mark.slow
def test_ablation_ordering_w6a6(dit_setup):
    """Table III: baseline >= +HO >= +HO+MRQ >= TQ-DiT in quantized-output
    error (allowing small noise at this toy scale)."""
    cfg, p, dif, sched, calib = dit_setup
    loss = dit_loss_fn(p, cfg)
    evalb = build_dit_calibration(p, cfg, dif, sched,
                                  lambda n, k: jax.random.normal(k, (n, 8, 8, 4)),
                                  jax.random.PRNGKey(77), n_per_group=8,
                                  batch=8)

    def eval_mse(qp):
        ctx = QuantContext(qparams=qp)
        tot = 0.0
        for b, g in evalb:
            fp = dit_apply(p, cfg, b["xt"], b["t"], b["y"])
            qt = dit_apply(p, cfg, b["xt"], b["t"], b["y"],
                           ctx=ctx.with_tgroup(g))
            tot += float(jnp.mean((fp - qt) ** 2))
        return tot / len(evalb)

    errs = {}
    for name in ["baseline", "+HO", "+HO+MRQ", "tq_dit"]:
        qcfg = SCHEMES[name](6, 6, tgq_groups=4, n_alpha=8, rounds=2)
        qp, _ = run_ptq(loss, calib, qcfg)
        errs[name] = eval_mse(qp)
    assert errs["tq_dit"] <= errs["baseline"] * 1.05
    assert errs["+HO+MRQ"] <= errs["baseline"] * 1.05


def test_w8a8_much_better_than_w4a4(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    loss = dit_loss_fn(p, cfg)
    b = calib[0][0]
    fp = dit_apply(p, cfg, b["xt"], b["t"], b["y"])

    def err(bits):
        qp, _ = run_ptq(loss, calib[:4],
                        PTQConfig(wbits=bits, abits=bits, tgq_groups=4,
                                  n_alpha=6, rounds=1))
        ctx = QuantContext(qparams=qp).with_tgroup(calib[0][1])
        q = dit_apply(p, cfg, b["xt"], b["t"], b["y"], ctx=ctx)
        return float(jnp.mean((fp - q) ** 2))

    assert err(8) < err(4)


def test_bias_correction_reduces_mean_shift(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    loss = dit_loss_fn(p, cfg)
    qp_plain, _ = run_ptq(loss, calib[:4],
                          PTQConfig(wbits=4, abits=4, use_fisher=False,
                                    use_mrq=False, use_tgq=False, n_alpha=6,
                                    rounds=1))
    qp_bc, _ = run_ptq(loss, calib[:4],
                       PTQConfig(wbits=4, abits=4, use_fisher=False,
                                 use_mrq=False, use_tgq=False,
                                 bias_correct=True, n_alpha=6, rounds=1))
    assert any("out_bias" in v for v in qp_bc.values())
    b = calib[0][0]
    fp = dit_apply(p, cfg, b["xt"], b["t"], b["y"])
    q1 = dit_apply(p, cfg, b["xt"], b["t"], b["y"],
                   ctx=QuantContext(qparams=qp_plain))
    q2 = dit_apply(p, cfg, b["xt"], b["t"], b["y"],
                   ctx=QuantContext(qparams=qp_bc))
    # bias correction should not hurt the mean error
    assert abs(float((q2 - fp).mean())) <= abs(float((q1 - fp).mean())) + 1e-4


def test_channel_balance_sets_prescale(dit_setup):
    cfg, p, dif, sched, calib = dit_setup
    qp, _ = run_ptq(dit_loss_fn(p, cfg), calib[:4],
                    PTQConfig(channel_balance=True, use_mrq=False,
                              use_tgq=False, n_alpha=6, rounds=1))
    assert any("x_prescale" in v and v["x_prescale"] is not None
               for v in qp.values())
