"""The range pipeline (``serving.quickcal.range_calibrate`` behind
``quantize(method="range")``): its capture reduces every statistic on the
device, so the host holds no more at 4 x 4 sampling than at 1 x 1; the
post-softmax split read from the captured histogram; and the spans and
counters ``quantize()`` leaves for tracing."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.contexts import prob_bin_edges, prob_histogram
from repro.core.quantizers import mrq_softmax_qdq
from repro.diffusion import DiffusionCfg, make_schedule
from repro.models import DiTCfg, dit_init
from repro.quant import QuantArtifact, QuantRecipe, quantize
from repro.serving.quickcal import range_calibrate, softmax_split

DIF = DiffusionCfg(T=40, tgq_groups=4)


def tiny(img_size):
    cfg = DiTCfg(img_size=img_size, in_ch=4, patch=2, d_model=32,
                 n_layers=1, n_heads=2, n_classes=8)
    p = dit_init(jax.random.PRNGKey(0), cfg)
    p["blocks"] = jax.tree.map(
        lambda a: a + jax.random.normal(jax.random.PRNGKey(1), a.shape) * 0.05,
        p["blocks"])
    return cfg, p


# ---------------------------------------------------------------------------
# the capture's host memory
# ---------------------------------------------------------------------------
def test_capture_host_bytes_do_not_grow_with_sampling_at_1k_tokens():
    """At 1,024 tokens one calibration batch of 4 carries 4 x 2 heads x
    1,024^2 probabilities (32 MiB in f32) at the attention site alone;
    the host sees only the per-group statistics, the same bytes at 1 x 1
    and 4 x 4."""
    cfg, p = tiny(64)
    sched = make_schedule(DIF)
    got = []
    for n, b in ((1, 1), (4, 4)):
        stats = {}
        range_calibrate(p, cfg, DIF, sched, jax.random.PRNGKey(0),
                        n_per_group=n, batch=b, max_rows=256, stats=stats)
        got.append(stats["capture_host_bytes"])
    assert got[0] == got[1]
    assert got[0] < 64 * 1024          # vs 32 MiB of probabilities a batch


# ---------------------------------------------------------------------------
# the post-softmax split
# ---------------------------------------------------------------------------
def _hist(p):
    return np.asarray(prob_histogram(jnp.asarray(p, jnp.float32)))


def _mse(p, s1):
    p = jnp.asarray(p, jnp.float32)
    return float(jnp.mean(jnp.square(p - mrq_softmax_qdq(p, s1, 8))))


def _softmax_rows(tokens, sigma, rows=256, seed=0):
    """Softmax rows over ``tokens`` keys of N(0, sigma^2) logits."""
    z = np.random.default_rng(seed).normal(0.0, sigma, (rows, tokens))
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def test_histogram_counts_and_sums_every_probability():
    p = _softmax_rows(64, 2.0)
    n, s, s2 = _hist(p).T
    assert n.sum() == p.size
    np.testing.assert_allclose(s.sum(), p.sum(), rtol=1e-4)
    np.testing.assert_allclose(s2.sum(), np.square(p).sum(), rtol=1e-4)
    edges = prob_bin_edges()
    j = int(np.argmax(n))
    inside = p[(p >= edges[j]) & (p < edges[j + 1])]
    assert n[j] == inside.size


@pytest.mark.parametrize("tokens", [64, 256, 1024])
@pytest.mark.parametrize("sigma", [0.5, 1.0, 2.0, 3.0])
def test_split_is_the_least_squared_error_on_its_grid(tokens, sigma):
    """Softmax rows of N(0, sigma^2) logits over ``tokens`` keys: the
    split chosen from the histogram is within 10% of the exact best of
    the same candidates (each bin edge as the end of region 1), measured
    on the probabilities themselves, and no worse than the fixed rule it
    replaces (region 1 ending at 8x the mean probability, 8 / tokens)."""
    p = _softmax_rows(tokens, sigma)
    s1, r2 = softmax_split(_hist(p))
    edges = prob_bin_edges()
    best = min(_mse(p, e / 128) for e in edges[edges >= 2.0 ** -16])
    assert _mse(p, s1) <= 1.1 * best
    assert _mse(p, s1) <= _mse(p, 8.0 / tokens / 128)
    assert r2 == pytest.approx(float(np.mean(p >= 128 * s1)), abs=2e-3)


@pytest.mark.parametrize("tokens", [256, 1024])
def test_split_gains_most_where_the_fixed_rule_sends_the_tail_coarse(tokens):
    """Logits of spread 2 put about 5% of the probabilities above 8/N.
    At 1,024 tokens 8/N is 1/128, the coarse step itself, so the fixed
    rule rounds every one of them at that step; the chosen split cuts the
    squared error about 6x there, about 2x at 256 tokens."""
    p = _softmax_rows(tokens, 2.0)
    s1, _ = softmax_split(_hist(p))
    gain = _mse(p, 8.0 / tokens / 128) / _mse(p, s1)
    assert gain > {256: 1.5, 1024: 4.0}[tokens]


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------
def test_quantize_opens_quant_spans_and_keeps_counters(monkeypatch, tmp_path):
    opened = []

    @contextlib.contextmanager
    def span(name, **tags):
        opened.append(("open", name, tags))
        yield
        opened.append(("close", name, tags))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", span)
    cfg, p = tiny(8)
    recipe = QuantRecipe(bits="w8a8", method="range", n_per_group=2,
                         calib_batch=1)
    art = quantize(p, cfg, DIF, recipe)
    names = [(k, n) for k, n, _ in opened if n.startswith("quant.")]
    G = DIF.tgq_groups
    assert names == ([("open", "quant.calibrate")]
                     + [("open", "quant.capture"), ("close", "quant.capture")]
                     * (2 * G)
                     + [("open", "quant.reduce"), ("close", "quant.reduce"),
                        ("close", "quant.calibrate"),
                        ("open", "quant.pack"), ("close", "quant.pack")])
    groups = [t["group"] for k, n, t in opened
              if n == "quant.capture" and k == "open"]
    assert groups == [g for g in range(G) for _ in range(2)]

    cal = art.meta["calib"]
    assert cal["capture_host_bytes"] > 0
    assert len(cal["softmax_s1"]) == G and len(cal["softmax_r2_share"]) == G
    for (lo, hi), r2 in zip(cal["softmax_s1"], cal["softmax_r2_share"]):
        assert 0 < lo <= hi <= 1 / 128 and 0.0 <= r2 <= 1.0
    text = art.summary()
    for k in ("capture_host_bytes=", "softmax_s1=[[", "softmax_r2_share=["):
        assert k in text
    art.save(str(tmp_path / "a"))
    assert QuantArtifact.load(str(tmp_path / "a")).meta["calib"] == cal
