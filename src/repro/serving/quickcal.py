"""Range-only calibration for serving bring-up and benchmarks.

The paper's full pipeline (Hessian-guided alternating search, Fisher
taps, R rounds — ``repro.core.ptq.run_ptq``) is the fidelity path and
costs minutes. Serving bring-up, smoke tests, and throughput benchmarks
only need *structurally correct* quantizers — per-group TGQ ranges in the
exact stacked ``(G, ...)`` format the fused int8 kernels gather — so this
module calibrates from plain min/max ranges in seconds:

- weights: per-output-channel symmetric ``ChannelQ`` from absmax,
- plain inputs: ``TGQ(UniformQ)`` — per-timestep-group [min, max] ranges,
- post-GELU/SiLU inputs: ``TGQ(MRQSignedQ)`` — per-group negative /
  positive lobe maxima (the two-region step sizes at alpha = 1),
- attention einsums (QK^T / P·V): per-group SYMMETRIC ``TGQ(SymQ)``
  absmax steps for q/k/v, and a per-group ``TGQ(MRQSoftmaxQ)`` region
  split for the post-softmax probs chosen from the captured probability
  histogram (``softmax_split``: the end of region 1 that least disturbs
  the attention output, read from the distribution alone — the same
  rule at 256 and at 1,024 tokens; the paper's searched s1 remains the
  fidelity pipeline's job). These pack via ``pack_int8_qk`` /
  ``pack_int8_pv`` so w8a8 serving runs the int8 attention kernels.

The capture (``core.contexts.RangeCaptureContext``) reduces every
statistic on the device as each eager calibration forward runs: input
[min, max] per linear, absmax per attention operand, and for the
probabilities a log-spaced histogram over [2^-24, 1] with the values'
mean and spread over the keys. The host receives those few numbers per
site and group, whatever the token count or the number of calibration
batches (at 1,024 tokens the probabilities alone are 64 MiB a sample a
layer). ``quantize()`` opens the spans ``quant.calibrate`` >
``quant.capture`` (one per batch, tagged ``group``) and
``quant.reduce``, and records the capture's counters in the artifact's
``calib`` metadata.

The result feeds ``repro.kernels.ops.convert_for_kernels`` directly; use
``run_ptq`` instead whenever sample quality is being measured.

This module is the 'range' pipeline BEHIND the unified API — call
``repro.quant.quantize(params, cfg, dif, QuantRecipe(method="range"))``
rather than this function directly; the artifact it returns packs,
serializes, and serves in one object.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calib import build_dit_calibration, dit_loss_fn
from repro.core.contexts import (
    RangeCaptureContext, RecordingContext, prob_bin_edges,
)
from repro.core.quantizers import (
    TGQ, ChannelQ, MRQSignedQ, MRQSoftmaxQ, SymQ, UniformQ,
    channel_scale_from_absmax, sym_scale_from_absmax,
    uniform_params_from_range, weight_absmax,
)
from repro.diffusion import DiffusionCfg, make_schedule
from repro.models import DiTCfg
from repro.quant.groups import resolve_group


def _nearest(groups, g):
    """Nearest calibrated group (shared contract: repro.quant.groups)."""
    return resolve_group(g, calibrated=groups)


def softmax_split(hist, v_ratio: float = 0.0, bits: int = 8
                  ) -> Tuple[float, float]:
    """The MRQ post-softmax split for one site and TGQ group, from the
    capture's probability histogram (``prob_histogram``: per bin of
    ``prob_bin_edges``, the count ``n``, sum p and sum p^2).

    Candidates put the end of region 1, ``T = 2^{k-1} s1``, on each bin
    edge up to 1 (region 1 everywhere, ``s1 = s2``). A wider region 1
    coarsens the fine step of the bulk near zero; a narrower one sends
    more of the tail to the fixed coarse step 1/2^{k-1}. The winner has
    the least expected squared error of the attention OUTPUT ``P V``:
    with ``v = mean + noise`` over the keys, a row's error is
    ``sum_k dp_k^2 |noise|^2 + (sum_k dp_k)^2 |mean|^2``, so the
    probabilities' squared error counts once and the square of each
    row's summed error (the mass rounded away or added; rows share it
    evenly, and a row's probabilities sum to 1, so the rows number
    ``sum p``) ``v_ratio = |mean|^2 / |noise|^2`` times. A bin narrower
    than its step is charged exactly, as if its values all rounded to the
    level ``k s`` nearest its centre (``sum (p - k s)^2 = sum p^2 -
    2 k s sum p + n (k s)^2``, summed error ``n k s - sum p``); a wider
    bin ``n s^2 / 12`` and no summed error. Returns ``(s1, share of
    probabilities at or above T)``. No token count or model constant
    enters: the rule reads only the captured statistics."""
    half = 2 ** (bits - 1)
    edges = prob_bin_edges()
    lo, hi = edges[:-1].copy(), edges[1:]
    lo[0] = 0.0                         # bin 0 takes everything below
    n, sp, sp2 = np.asarray(hist, np.float64).T
    total, rows = float(n.sum()), float(sp.sum())
    if total <= 0 or rows <= 0:
        return 1.0 / half, 0.0
    centre = np.sqrt(lo * hi)

    def charge(step, kmax):
        """(squared error, summed error) of each bin at ``step``; a
        column of steps gives a row per candidate, a column per bin."""
        k = np.minimum(np.round(centre / step), kmax)
        narrow = hi - lo <= step
        sq = np.where(narrow, np.maximum(sp2 - 2 * k * step * sp
                                         + n * (k * step) ** 2, 0.0),
                      n * step * step / 12.0)
        return sq, np.where(narrow, n * k * step - sp, 0.0)

    # candidate t (edge t ends region 1): bins below t at s1 = edge / half,
    # bins from t on at the coarse step
    steps = edges[1:, None] / half
    below = np.arange(len(n))[None, :] < np.arange(1, len(edges))[:, None]
    sq1, sum1 = charge(steps, half - 1)
    sq2, sum2 = charge(np.asarray([[1.0 / half]]), half)
    sq = np.where(below, sq1, sq2).sum(axis=1)
    summed = np.where(below, sum1, sum2).sum(axis=1)
    t = 1 + int(np.argmin(sq + v_ratio * summed * summed / rows))
    return float(edges[t] / half), float(n[t:].sum() / total)


def range_calibrate(params, dcfg: DiTCfg, dif: DiffusionCfg, sched=None,
                    key=None, *, wbits: int = 8, abits: int = 8,
                    n_per_group: int = 2, batch: int = 2,
                    max_rows: int = 128, stats: Optional[dict] = None
                    ) -> Tuple[Dict[str, dict], Dict[str, np.ndarray]]:
    """Min/max calibration of every DiT linear, time-grouped.

    Runs ``n_per_group`` forward-diffused samples per TGQ group through
    the model eagerly (``RangeCaptureContext``: every statistic reduced
    on the device), then derives quantizer params from ranges, and the
    post-softmax split from the probability histogram
    (``softmax_split``). Groups with no captured rows borrow the nearest
    calibrated group, so stacked params always cover all
    ``dif.tgq_groups``.

    Returns ``(qparams, weights)`` — exactly the two arguments
    ``convert_for_kernels`` wants. A ``stats`` dict is filled with the
    capture's counters: ``capture_host_bytes`` (the statistics the
    capture brought to the host; it held nothing else there),
    ``softmax_s1`` (per group, ``[min, max]`` of s1 over attention
    sites) and ``softmax_r2_share`` (per group, the share of calibration
    probabilities at or above the split).
    """
    sched = sched if sched is not None else make_schedule(dif)
    key = key if key is not None else jax.random.PRNGKey(0)
    loss = dit_loss_fn(params, dcfg)

    x0 = lambda n, k: jax.random.normal(
        k, (n, dcfg.img_size, dcfg.img_size, dcfg.in_ch))
    calib = build_dit_calibration(params, dcfg, dif, sched, x0, key,
                                  n_per_group=n_per_group, batch=batch)

    rec = RecordingContext()
    loss(rec, calib[0][0])
    cap = RangeCaptureContext(registry=rec.registry,
                              max_rows_per_batch=max_rows)
    for b, tg in calib:
        with jax.profiler.TraceAnnotation("quant.capture", group=int(tg)):
            cap.begin_batch()
            loss(dataclasses.replace(cap, tgroup=tg), b)
    with jax.profiler.TraceAnnotation("quant.reduce"):
        st = cap.reduce()

    G = dif.tgq_groups
    half = 2 ** (abits - 1)
    qparams: Dict[str, dict] = {}

    def per_group(per: Dict[int, Any], f):
        """``f`` of each group's statistic, borrowed from the nearest
        calibrated group where a group has none."""
        groups = sorted(per)
        return [f(per[_nearest(groups, g)]) for g in range(G)]

    def sym(per):
        absmax = per_group(per, lambda a: max(float(a[0]), 1e-6))
        return TGQ(SymQ(scale=sym_scale_from_absmax(
            jnp.asarray(absmax, jnp.float32), abits), bits=abits))

    # ---- attention einsums: symmetric q/k/v + histogram-chosen probs split -
    splits = []
    for name, info in rec.registry.items():
        if (info.kind != "einsum" or info.b_is_weight
                or name not in st):
            continue
        if info.a_kind == "post_softmax":
            hist, mom = st[name]["p_hist"], st[name]["v_moments"]
            split = per_group({g: (h, mom[g]) for g, h in hist.items()},
                              lambda hm: softmax_split(
                                  hm[0], hm[1][0] / max(hm[1][1], 1e-30),
                                  abits))
            splits.append((split, per_group(hist, lambda h: h[:, 0].sum())))
            xq: Any = TGQ(MRQSoftmaxQ(
                s1=jnp.asarray([s for s, _ in split], jnp.float32),
                bits=abits))
        else:
            xq = sym(st[name]["a"])
        qparams[name] = {"x": xq, "b": sym(st[name]["b"])}

    # ---- linears: per-group ranges --------------------------------------
    for name, info in rec.registry.items():
        if info.kind != "linear" or name not in st:
            continue
        lo_hi = per_group(st[name]["x"], lambda r: (-float(r[0]),
                                                     float(r[1])))

        if info.a_kind in ("post_gelu", "post_silu"):
            xq: Any = TGQ(MRQSignedQ(
                s_neg=jnp.asarray([max(-lo, 1e-6) / half for lo, _ in lo_hi],
                                  jnp.float32),
                s_pos=jnp.asarray([max(hi, 1e-6) / half for _, hi in lo_hi],
                                  jnp.float32),
                bits=abits))
        else:
            scales, zeros = [], []
            for lo, hi in lo_hi:
                s, z = uniform_params_from_range(jnp.float32(lo),
                                                 jnp.float32(hi), abits)
                scales.append(s)
                zeros.append(z)
            xq = TGQ(UniformQ(scale=jnp.stack(scales), zero=jnp.stack(zeros),
                              bits=abits))

        w = cap.weights[name]
        qparams[name] = {
            "x": xq,
            "w": ChannelQ(channel_scale_from_absmax(
                weight_absmax(jnp.asarray(w)), wbits), bits=wbits),
        }
    if stats is not None:
        stats["capture_host_bytes"] = sum(a.nbytes
                                          for a in jax.tree.leaves(st))
        if splits:
            stats["softmax_s1"] = [
                [min(sp[g][0] for sp, _ in splits),
                 max(sp[g][0] for sp, _ in splits)] for g in range(G)]
            stats["softmax_r2_share"] = [
                float(sum(sp[g][1] * n[g] for sp, n in splits)
                      / max(sum(n[g] for _, n in splits), 1))
                for g in range(G)]
    return qparams, cap.weights
