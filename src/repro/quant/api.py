"""`quantize()` — the single public quantization entrypoint.

One call replaces the four hand-wired chains the repo grew
(``run_ptq``/``range_calibrate`` -> qparams dict + captured weights ->
``convert_for_kernels`` -> ``make_quant_context``):

    recipe = QuantRecipe(bits="w8a8", method="range")
    artifact = quantize(params, dcfg, dif, recipe)
    engine = ServeEngine.from_artifact(params, artifact, mesh=mesh)
    # ... later, in a fresh process (no recalibration):
    artifact.save("/ckpts/dit_w8a8")
    artifact = QuantArtifact.load("/ckpts/dit_w8a8")

Dispatch is by ``recipe.method``: 'range' runs
``serving.quickcal.range_calibrate`` (seconds; structurally correct TGQ
ranges), 'ho' runs the paper's full Algorithm 1
(``core.ptq.run_ptq`` — Fisher taps + alternating candidate search).
Either way, results are packed for the Pallas kernel family matching the
recipe's bit-width (``kernels.ops.convert_for_kernels``: w8a8/w6a6 ->
fused int8 kernels, w4a4 -> nibble-packed int4 kernels) before the
artifact is built, so ``artifact.context()`` serves through the
deployment path by default.

Internal dispatch imports are deferred into the function body:
``kernels.ops`` and ``serving.quickcal`` themselves import
``repro.quant.groups``, and top-level imports here would cycle.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import dataclasses

import jax

from repro.quant.artifact import ARTIFACT_VERSION, QuantArtifact
from repro.quant.groups import group_boundaries
from repro.quant.recipe import QuantRecipe


def quantize(params, model_cfg, dif_cfg, recipe: QuantRecipe,
             calib_data: Optional[List[Tuple[Dict[str, Any], int]]] = None,
             *, sched=None, provenance: Optional[dict] = None
             ) -> QuantArtifact:
    """Calibrate + search + pack in one call; returns a QuantArtifact.

    params / model_cfg : the DiT model (``model_cfg`` a ``DiTCfg``).
    dif_cfg            : ``DiffusionCfg``; ``recipe.tgq_groups`` (if set)
                         overrides its group count — the artifact records
                         the effective configs either way.
    calib_data         : optional Phase-1 batches ``[(batch_dict, group)]``
                         (``core.calib.build_dit_calibration`` output) for
                         the 'ho' method. ``None`` builds a synthetic
                         Gaussian-latent set sized by
                         ``recipe.n_per_group`` / ``recipe.calib_batch``.
                         The 'range' method always draws its own capture
                         set (its protocol is part of the method).
    sched              : diffusion schedule (built from ``dif_cfg`` if
                         omitted).
    provenance         : caller-supplied metadata recorded verbatim under
                         ``meta["provenance"]`` — git sha, timestamp,
                         arch label, cluster name. The API does not guess
                         these (no clock/VCS access here); deployments
                         that want them pass them in.
    """
    from repro.diffusion import make_schedule

    if recipe.tgq_groups is not None \
            and recipe.tgq_groups != dif_cfg.tgq_groups:
        if calib_data is not None:
            # the batches' group tags were computed under the CALLER's
            # group boundaries; reinterpreting them under a different G
            # would silently miscalibrate every stacked row.
            raise ValueError(
                f"recipe.tgq_groups={recipe.tgq_groups} overrides "
                f"dif_cfg.tgq_groups={dif_cfg.tgq_groups} but calib_data "
                "was supplied — build the calibration under the intended "
                "group count (set dif_cfg.tgq_groups) instead")
        dif_cfg = dataclasses.replace(dif_cfg, tgq_groups=recipe.tgq_groups)
    if calib_data is not None:
        bad = sorted({int(tg) for _, tg in calib_data
                      if not 0 <= int(tg) < dif_cfg.tgq_groups})
        if bad:
            raise ValueError(
                f"calib_data group tags {bad} out of range for "
                f"tgq_groups={dif_cfg.tgq_groups}")
    if recipe.method == "range":
        defaults = QuantRecipe()
        unsupported = [f for f in ("skip_patterns", "weight_only_patterns",
                                   "use_mrq", "use_tgq", "use_fisher",
                                   "rounds", "n_alpha", "fisher_norm",
                                   "bias_correct", "channel_balance",
                                   "balance_alpha")
                       if getattr(recipe, f) != getattr(defaults, f)]
        if unsupported:
            # range_calibrate has no such knobs; embedding them in the
            # artifact's recipe would record a calibration that never
            # happened — and the load-time expect_recipe guard would then
            # ratify the false description (or spuriously reject a true
            # one). A range recipe keeps every HO-only field at default.
            raise ValueError(
                f"QuantRecipe(method='range') cannot honor {unsupported}: "
                "the range pipeline always quantizes every op with the "
                "full MRQ+TGQ structure and runs no search — use "
                "method='ho' for these knobs")
    sched = sched if sched is not None else make_schedule(dif_cfg)
    key = jax.random.PRNGKey(recipe.seed)

    with jax.profiler.TraceAnnotation("quant.calibrate"):
        if recipe.method == "range":
            from repro.serving.quickcal import range_calibrate
            calib_stats: Dict[str, Any] = {}
            qparams, weights = range_calibrate(
                params, model_cfg, dif_cfg, sched, key,
                wbits=recipe.wbits, abits=recipe.abits,
                n_per_group=recipe.n_per_group, batch=recipe.calib_batch,
                max_rows=recipe.max_rows_per_batch, stats=calib_stats)
            calib_stats = {"n_quantized": len(qparams), **calib_stats}
        else:                                           # "ho"
            from repro.core.calib import build_dit_calibration, dit_loss_fn
            from repro.core.ptq import run_ptq
            if calib_data is None:
                x0 = lambda n, k: jax.random.normal(
                    k, (n, model_cfg.img_size, model_cfg.img_size,
                        model_cfg.in_ch))
                calib_data = build_dit_calibration(
                    params, model_cfg, dif_cfg, sched, x0, key,
                    n_per_group=recipe.n_per_group, batch=recipe.calib_batch)
            qparams, report = run_ptq(dit_loss_fn(params, model_cfg),
                                      calib_data,
                                      recipe.ptq_config(dif_cfg.tgq_groups))
            weights = report.pop("weights")  # full fp copy — never persisted
            calib_stats = {k: v for k, v in report.items()
                           if isinstance(v, (int, float, str))}

    if recipe.kernel_deployable:
        from repro.kernels.ops import convert_for_kernels
        with jax.profiler.TraceAnnotation("quant.pack"):
            qparams = convert_for_kernels(qparams, weights)

    from repro.checkpoint import ckpt
    meta = {
        "format_version": ARTIFACT_VERSION,
        "model": {"class": type(model_cfg).__name__,
                  "cfg": dataclasses.asdict(model_cfg)},
        # content identity of the fp tree this calibration ran against —
        # from_artifact / load(params=...) fail fast on any other params
        "params_hash": ckpt.content_hash(params),
        "dif": dataclasses.asdict(dif_cfg),
        "tgq_groups": dif_cfg.tgq_groups,
        "tgq_group_boundaries": [list(b) for b in group_boundaries(
            dif_cfg.T, dif_cfg.tgq_groups)],
        "calib": calib_stats,
        # content identity of the recipe itself — the autotune ledger key,
        # recorded so a loaded artifact names the exact configuration
        # that produced it without re-deriving the hash
        "recipe_hash": recipe.content_hash(),
        "provenance": dict(provenance or {}),
    }
    return QuantArtifact(qparams=qparams, recipe=recipe, meta=meta)
