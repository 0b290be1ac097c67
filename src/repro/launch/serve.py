"""Serving launcher — a thin CLI over ``repro.serving``.

DiT archs run through the sharded batched serving subsystem: a request
stream is coalesced into fixed-shape microbatches (step-bucketed, padded,
CFG-paired) and executed data-parallel via shard_map; ``--quantize``
serves through the Pallas kernel family for the chosen bits (w8a8/w6a6:
fused int8 kernels; w4a4: nibble-packed int4 kernels). LM archs keep the
simple batched-decode path.

Quantized serving goes through the unified API (``repro.quant``):
``--quantize w8a8`` builds a ``QuantRecipe``, runs ``quantize()`` and
serves the returned ``QuantArtifact``; ``--save-artifact DIR`` persists
it, and ``--load-artifact DIR`` cold-starts a later process from disk —
the expensive calibration never reruns, and the served samples are
bit-identical to the calibrating process (asserted in
``tests/test_quant_api.py``).

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.serve --arch dit-xl-2 --smoke \
      --requests 8 --microbatch 4 --steps 4 --quantize w8a8
  PYTHONPATH=src python -m repro.launch.serve --arch dit-xl-2 --smoke \
      --requests 8 --microbatch 4 --steps 4 --quantize w8a8 \
      --save-artifact /tmp/dit_w8a8
  PYTHONPATH=src python -m repro.launch.serve --arch dit-xl-2 --smoke \
      --requests 8 --microbatch 4 --steps 4 --quantize w8a8 \
      --load-artifact /tmp/dit_w8a8
  PYTHONPATH=src python -m repro.launch.serve --arch dit-xl-2 --smoke \
      --requests 8 --dp 2 --cfg-scale 1.5
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt_len 32 --gen 16

``--dp N`` forces N host devices (XLA_FLAGS) for data-parallel serving on
CPU; it must be set before jax initializes, which is why all jax imports
live inside ``main``.
"""
from __future__ import annotations

import argparse
import os
import time
import warnings


def fake_quant_fallback_warning(artifact) -> "str | None":
    """The message served when a quantized artifact cannot lower (fully)
    onto the Pallas kernels, or None when every quantized matmul runs a
    kernel. Two shapes of failure, both said out loud:

    - no packs at all (an artifact from an older writer): the whole
      serve is fake-quant;
    - PARTIAL packs: ``artifact.fallback_ops()`` is non-empty — the
      message names exactly which op ids fell back and how many, so a
      deploy log never hides a per-op fp island. Since prescale folding
      landed, ``channel_balance=True`` recipes pack everything and this
      returns None.

    A named helper so the no-silent-fallback contract is testable
    without spinning up an engine: every --quantize/--load-artifact
    serve either runs the packed kernels or says which ops do not.
    """
    if not artifact.has_kernel_packs:
        return (
            f"artifact {artifact.recipe.bits}/{artifact.recipe.method} "
            "carries no kernel packs: serving falls back to the FAKE-QUANT "
            "path (simulated quant-dequant in fp32 — no int8/int4 Pallas "
            "kernels, no weight-traffic win). Re-quantize with a "
            "kernel-deployable recipe (w8a8/w6a6 -> fused int8 kernels, "
            "w4a4 -> packed int4) for the deployment path.")
    fb = artifact.fallback_ops()
    if not fb:
        return None
    shown = ", ".join(fb[:8]) + (", ..." if len(fb) > 8 else "")
    return (
        f"artifact {artifact.recipe.bits}/{artifact.recipe.method}: "
        f"{len(fb)} quantized op(s) carry no kernel pack and fall back to "
        f"the FAKE-QUANT path: {shown}. Every other op runs the Pallas "
        "kernels; re-quantize to clear the residue.")


def _warn_if_fake_quant(artifact) -> None:
    msg = fake_quant_fallback_warning(artifact)
    if msg is not None:
        warnings.warn(msg, RuntimeWarning, stacklevel=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4, help="LM decode batch")
    ap.add_argument("--prompt_len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8,
                    help="DiT: number of generation requests to serve")
    ap.add_argument("--microbatch", type=int, default=4,
                    help="DiT: slots per compiled microbatch")
    ap.add_argument("--steps", type=int, default=25, help="DiT sample steps")
    ap.add_argument("--cfg-scale", type=float, default=1.0,
                    help="classifier-free guidance scale (1 = conditional)")
    ap.add_argument("--dp", type=int, default=0,
                    help="force N host devices for data-parallel serving "
                         "(0 = use whatever the backend exposes)")
    # NOTE: argparse compares the supplied value against `choices` AFTER
    # applying `type`; a None inside choices only matches when the flag is
    # omitted entirely, and `--quantize` with no sane sentinel rejected the
    # default-unset path on some invocations. "none" is the sentinel.
    ap.add_argument("--quantize", default="none",
                    choices=("none", "w8a8", "w6a6", "w4a4"))
    ap.add_argument("--calib", default="range", choices=("range", "ho"),
                    help="calibration: fast range-only (serving "
                         "bring-up) or the paper's full HO search")
    ap.add_argument("--attn-impl", default=None,
                    choices=("flash", "composed"),
                    help="attention lowering: 'flash' = one fused "
                         "Pallas kernel (default; no (S,S) HBM "
                         "round-trip), 'composed' = the three-kernel "
                         "exactness oracle. Unset keeps the recipe/"
                         "artifact default")
    ap.add_argument("--save-artifact", default=None, metavar="DIR",
                    help="after calibrating, persist the QuantArtifact "
                         "(qparams + int8 packs + recipe + provenance) so "
                         "later processes cold-start with --load-artifact")
    ap.add_argument("--load-artifact", default=None, metavar="DIR",
                    help="serve from a saved QuantArtifact — NO calibration "
                         "runs in this process; with --quantize the "
                         "artifact's recorded bits must match")
    ap.add_argument("--dump-samples", default=None, metavar="NPY",
                    help="np.save the served samples (request-id order) — "
                         "used by tests to assert bit-identity across "
                         "artifact save/load")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="DiT: serve through the fault-tolerant async "
                         "continuous-batching engine (slot pool, chunked "
                         "dispatches, NaN quarantine, deadlines) instead "
                         "of the synchronous step-bucketed path; samples "
                         "are bit-identical either way. Composes with "
                         "--dp N: the slot pool shards across the "
                         "data-parallel mesh (microbatch must divide by N)")
    ap.add_argument("--chunk", type=int, default=4,
                    help="async: denoising steps advanced per compiled "
                         "dispatch (the admission/cancellation granularity)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="async: per-request deadline; requests not "
                         "finished by a chunk boundary past it are "
                         "CANCELLED (structured outcome, slot freed)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="async: NaN-quarantine retries per request before "
                         "a structured FAILED outcome")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.save_artifact is not None and (args.quantize == "none"
                                           or args.load_artifact is not None):
        ap.error("--save-artifact requires --quantize (and excludes "
                 "--load-artifact): there is no freshly calibrated "
                 "artifact to save otherwise")
    if args.dp > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.dp}")

    import jax
    import numpy as np

    from repro.configs import get, get_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import DiTCfg, lm_init, lm_generate
    from repro.nn.ctx import FPContext

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    key = jax.random.PRNGKey(args.seed)
    ctx = FPContext()

    if isinstance(cfg, DiTCfg):
        from repro.diffusion import DiffusionCfg, make_schedule
        from repro.launch.mesh import make_serving_mesh
        from repro.models import dit_init
        from repro.serving import AsyncServeEngine, RequestScheduler, \
            ServeEngine

        params = dit_init(key, cfg)
        dif = DiffusionCfg(T=1000)
        sched = make_schedule(dif)
        mesh = make_serving_mesh()
        artifact = None
        deadline_s = (args.deadline_ms / 1000.0
                      if args.deadline_ms is not None else None)
        # async + dp: the slot pool shards across the same DP mesh as the
        # sync path (one slot-pool slice per device, shard_map'd chunks)
        async_kw = dict(mesh=mesh if args.dp > 1 else None,
                        microbatch=args.microbatch,
                        step_buckets=(args.steps,), chunk=args.chunk,
                        max_retries=args.max_retries, deadline_s=deadline_s)

        if args.load_artifact is not None:
            # cold-start: the saved artifact IS the calibration — nothing
            # is recalibrated in this process.
            from repro.quant import QuantArtifact
            t0 = time.perf_counter()
            artifact = QuantArtifact.load(args.load_artifact)
            if args.quantize != "none" \
                    and artifact.recipe.bits != args.quantize:
                raise SystemExit(
                    f"--quantize {args.quantize} but artifact at "
                    f"{args.load_artifact} was calibrated at "
                    f"{artifact.recipe.bits} ({artifact.summary()})")
            print(f"loaded {artifact.summary()} in "
                  f"{time.perf_counter() - t0:.1f}s — no calibration run")
            _warn_if_fake_quant(artifact)
            # no sched= here: the artifact's recorded DiffusionCfg is the
            # source of truth (the CLI-built schedule would silently win
            # over an artifact calibrated under a different chain)
            if args.async_mode:
                engine = AsyncServeEngine.from_artifact(
                    params, artifact, attn_impl=args.attn_impl, **async_kw)
            else:
                engine = ServeEngine.from_artifact(
                    params, artifact, mesh=mesh, attn_impl=args.attn_impl,
                    microbatch=args.microbatch, step_buckets=(args.steps,))
        else:
            if args.quantize != "none":
                from repro.quant import QuantRecipe, quantize
                # HO-only knobs stay at defaults for --calib range: the
                # recipe must describe what ran (quantize() enforces it)
                ho_kw = {"n_alpha": 8, "rounds": 2} \
                    if args.calib == "ho" else {}
                if args.attn_impl is not None:
                    ho_kw["attn_impl"] = args.attn_impl
                recipe = QuantRecipe(bits=args.quantize, method=args.calib,
                                     seed=args.seed, **ho_kw)
                t0 = time.perf_counter()
                artifact = quantize(params, cfg, dif, recipe, sched=sched,
                                    provenance={"arch": args.arch,
                                                "smoke": args.smoke})
                print(f"{args.calib}-calibrated {artifact.summary()} in "
                      f"{time.perf_counter() - t0:.1f}s")
                _warn_if_fake_quant(artifact)
                ctx = artifact.context()      # packed kernels iff packs exist
                if args.save_artifact is not None:
                    artifact.save(args.save_artifact)
                    print(f"saved artifact -> {args.save_artifact}")
            if args.async_mode:
                engine = AsyncServeEngine(params, cfg, dif, sched, ctx=ctx,
                                          **async_kw)
            else:
                engine = ServeEngine(params, cfg, dif, sched, ctx=ctx,
                                     mesh=mesh, microbatch=args.microbatch,
                                     step_buckets=(args.steps,))
        rkey = jax.random.PRNGKey(args.seed + 1)
        labels = jax.random.randint(rkey, (args.requests,), 0, cfg.n_classes)

        if args.async_mode:
            t0 = time.perf_counter()
            for i in range(args.requests):
                engine.submit(int(labels[i]), steps=args.steps,
                              cfg_scale=args.cfg_scale,
                              seed=args.seed * 100_000 + i)
            outcomes = engine.run_until_drained()
            dt = time.perf_counter() - t0
            ok = {r: o for r, o in outcomes.items() if o.status == "OK"}
            samples = np.stack([ok[r].sample for r in sorted(ok)])
            if args.dump_samples is not None:
                np.save(args.dump_samples, samples)
                print(f"dumped {samples.shape} samples -> "
                      f"{args.dump_samples}")
            st, m = engine.stats, engine.metrics()
            print(f"async-served {len(outcomes)} requests x {args.steps} "
                  f"steps (chunk={args.chunk}) in {dt:.2f}s: "
                  f"{m['by_status']}, goodput {m['goodput_rps']:.2f} ok/s, "
                  f"latency p50/p99 {m['latency_p50_s']:.2f}/"
                  f"{m['latency_p99_s']:.2f}s, queue-wait p50 "
                  f"{m['queue_wait_p50_s']:.2f}s")
            print(f"{st['dispatches']} dispatches, {st['chunk_traces']} "
                  f"chunk trace(s), {st['retries']} retries, "
                  f"{len(st['degradations'])} degradations")
            for d in st["degradations"]:
                print(f"degraded: {d['reason']} (after {d['error']})")
            print(f"sample mean={samples.mean():.4f} "
                  f"std={samples.std():.4f}")
            return

        sched_q = RequestScheduler(microbatch=args.microbatch,
                                   step_buckets=(args.steps,),
                                   n_classes=cfg.n_classes)
        for i in range(args.requests):
            sched_q.submit(int(labels[i]), steps=args.steps,
                           cfg_scale=args.cfg_scale,
                           seed=args.seed * 100_000 + i)
        t0 = time.perf_counter()
        results = sched_q.run(engine)
        dt = time.perf_counter() - t0
        samples = np.stack([results[r].sample for r in sorted(results)])
        if args.dump_samples is not None:
            np.save(args.dump_samples, samples)
            print(f"dumped {samples.shape} samples -> {args.dump_samples}")
        st = engine.stats
        print(f"served {len(results)} requests x {args.steps} steps on "
              f"{jax.device_count()} device(s) in {dt:.2f}s "
              f"({len(results) / dt:.2f} req/s, "
              f"{dt / (st['microbatches'] * args.steps) * 1000:.0f} ms/step); "
              f"{st['microbatches']} microbatches, "
              f"{st['padded_slots']} padded slots, "
              f"buckets compiled: {st['compiled_buckets']}")
        print(f"sample mean={samples.mean():.4f} std={samples.std():.4f}")
        return

    if args.save_artifact or args.load_artifact or args.dump_samples:
        raise SystemExit(
            f"--save-artifact/--load-artifact/--dump-samples are DiT-only "
            f"({args.arch} takes the LM decode path, which has no artifact "
            "support); drive LM PTQ via repro.core.run_ptq for now")
    params = lm_init(key, cfg)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab)
    t0 = time.perf_counter()
    toks = lm_generate(params, cfg, prompts, args.gen, ctx=ctx,
                       max_len=args.prompt_len + args.gen)
    toks.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"generated {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({dt/args.gen*1000:.0f} ms/token batched)")
    print("sample:", np.asarray(toks[0])[:16])


if __name__ == "__main__":
    main()
