"""The serving engine: compiled, sharded microbatch execution.

One :class:`ServeEngine` owns

- the model (params + DiTCfg) and the execution context — ``FPContext``
  for fp32, a fake-quant ``QuantContext`` for fidelity serving, or
  ``QuantContext(kernel=True)`` with int8-packed qparams for the fused
  Pallas deployment path,
- the diffusion setup (``DiffusionCfg`` + schedule),
- a data-parallel mesh: the paired sampler is wrapped in ``shard_map``
  with params replicated (``P()``) and every per-request array sharded on
  the DP super-axis (``repro.distributed.request_spec``). The model
  forward has no cross-sample communication, so serving scales linearly
  across the "data" axis and each device runs the SAME executable a
  single-device engine would — bit-identical samples either way
  (``benchmarks/serve_throughput.py`` asserts this).
- a cache of compiled executables, one per step bucket. TGQ group
  selection happens inside the fused kernels (scalar-prefetched group
  index), so all timestep groups share one executable; only a new step
  bucket triggers a compile. With int8-packed qparams the executable
  contains the WHOLE quantized block: fused int8 linears AND the int8
  attention path — by default ONE flash-style kernel per block
  (``kernels.flash_attn_mrq``: int8 QK^T -> online softmax -> MRQ codes
  -> P·V, the (S,S) scores/codes never touching HBM), or the composed
  three-kernel chain under ``attn_impl="composed"`` — so the DDPM scan
  stays one compiled program with no fp attention island inside.

``check_vma=False`` on the shard_map is required: pallas_call has no
varying-manual-axes rule, and the body is embarrassingly data-parallel
anyway.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.diffusion import DiffusionCfg, ddpm_sample_paired, make_schedule
from repro.diffusion.ddpm import (
    ddpm_chunk_slots, ddpm_init_latent, make_slot_schedule,
)
from repro.distributed import batch_spec, dp_size, replicated, request_spec
from repro.models import DiTCfg, dit_apply
from repro.nn.ctx import FPContext
from repro.serving import lifecycle as lc
from repro.serving.batching import (
    DEFAULT_STEP_BUCKETS, GenRequest, GenResult, MicroBatch, bucket_steps,
    coalesce,
)
from repro.serving.faults import EngineFault, degrade_context
from repro.serving.scheduler import validate_label


def _ctx_arrays(ctx, mesh: Optional[Mesh]):
    """The op context's arrays (replicated on ``mesh``) and its rebuild
    function: executables take a quantized model's weight codes as
    arguments, not as compiled-in constants (``OpContext.split_arrays``)."""
    arrays, rebuild = ctx.split_arrays()
    if mesh is not None:
        arrays = jax.device_put(arrays, replicated(mesh))
    return arrays, rebuild


# the pump's phases timed into ``AsyncServeEngine.stats["phase_s"]``
PHASES = ("admit", "dispatch", "wait", "resolve", "pull")


class ServeEngine:
    """Executes fixed-shape microbatches of DiT generation requests.

    Parameters
    ----------
    params, dcfg : the DiT model.
    dif, sched   : diffusion config + schedule (sched built if omitted).
    ctx          : op context (default fp32). Pass a quantization
                   artifact's ``artifact.context()`` for the fused-int8
                   serving path — or build the whole engine with
                   :meth:`from_artifact`.
    mesh         : data-parallel mesh (``make_serving_mesh()``). None runs
                   un-sharded on the default device.
    microbatch   : slots per microbatch; must divide by the mesh's DP size.
    step_buckets : allowed scan lengths (compile keys).
    """

    def __init__(self, params, dcfg: DiTCfg, dif: DiffusionCfg,
                 sched=None, *, ctx=None, mesh: Optional[Mesh] = None,
                 microbatch: int = 8,
                 step_buckets: Sequence[int] = DEFAULT_STEP_BUCKETS,
                 clip_x0: Optional[float] = None):
        self.dcfg = dcfg
        self.dif = dif
        self.sched = sched if sched is not None else make_schedule(dif)
        self.ctx = ctx if ctx is not None else FPContext()
        self.mesh = mesh
        self.microbatch = int(microbatch)
        self.step_buckets = tuple(sorted(int(b) for b in step_buckets))
        self.clip_x0 = clip_x0
        if mesh is not None:
            nd = dp_size(mesh)
            if self.microbatch % nd != 0:
                raise ValueError(
                    f"microbatch {self.microbatch} not divisible by the "
                    f"mesh's {nd} data-parallel shards")
            params = jax.device_put(params, replicated(mesh))
        self.params = params
        self._qargs, self._rebuild_ctx = _ctx_arrays(self.ctx, mesh)
        self._fns: Dict[int, Any] = {}          # step bucket -> compiled fn
        self.stats: Dict[str, Any] = {
            "compiled_buckets": [], "microbatches": 0, "requests": 0,
            "padded_slots": 0, "wall_s": 0.0,
        }

    @classmethod
    def from_artifact(cls, params, artifact, *, kernel=None,
                      attn_impl: Optional[str] = None, sched=None,
                      mesh: Optional[Mesh] = None, microbatch: int = 8,
                      step_buckets: Sequence[int] = DEFAULT_STEP_BUCKETS,
                      clip_x0: Optional[float] = None) -> "ServeEngine":
        """Quantized engine straight from a ``repro.quant.QuantArtifact``
        — the cold-start path: ``QuantArtifact.load(path)`` then this, no
        calibration in the serving process.

        The artifact supplies the model/diffusion configs and the op
        context (``artifact.context(kernel=..., attn_impl=...)``: fused
        int8 kernels when packs exist, fake-quant otherwise;
        ``attn_impl=None`` serves the attention lowering the artifact's
        recipe records — 'flash' single-kernel by default, 'composed'
        for the three-kernel oracle); ``params`` are the fp model
        weights (artifacts carry quantizer state and int8 weight codes,
        never the fp tree). Two identity guards fail fast here rather
        than as garbage samples inside the compiled sampler: a d_model
        mismatch against the artifact's recorded config, and — when the
        artifact records an fp-params content hash — any params tree
        other than the one the calibration ran against
        (``artifact.check_params``).
        """
        artifact.check_params(params)
        dcfg = artifact.model_cfg()
        d_model = params.get("x_proj", {}).get("w", None) if isinstance(
            params, dict) else None
        if d_model is not None and d_model.shape[-1] != dcfg.d_model:
            raise ValueError(
                f"params d_model {d_model.shape[-1]} != artifact's recorded "
                f"DiTCfg.d_model {dcfg.d_model} — wrong checkpoint for this "
                "artifact?")
        return cls(params, dcfg, artifact.dif_cfg(), sched,
                   ctx=artifact.context(kernel=kernel, attn_impl=attn_impl),
                   mesh=mesh, microbatch=microbatch,
                   step_buckets=step_buckets, clip_x0=clip_x0)

    # -- executable construction -------------------------------------------
    def _build(self, steps: int):
        dcfg, dif, sched = self.dcfg, self.dif, self.sched
        rebuild, clip = self._rebuild_ctx, self.clip_x0
        null_label = dcfg.n_classes                # the extra embedding row

        def run(params, qargs, labels, seeds, guidance):
            eps = lambda x, t, y, c: dit_apply(params, dcfg, x, t, y, ctx=c)
            shape = (labels.shape[0], dcfg.img_size, dcfg.img_size,
                     dcfg.in_ch)
            return ddpm_sample_paired(eps, dif, sched, shape, labels, seeds,
                                      guidance, null_label=null_label,
                                      steps=steps, ctx=rebuild(qargs),
                                      clip_x0=clip)

        if self.mesh is not None:
            rspec = request_spec(self.mesh)
            run = jax.shard_map(run, mesh=self.mesh,
                                in_specs=(P(), P(), rspec, rspec, rspec),
                                out_specs=batch_spec(self.mesh, 4),
                                check_vma=False)
        return jax.jit(run)

    def _fn(self, steps: int):
        if steps not in self._fns:
            self._fns[steps] = self._build(steps)
            self.stats["compiled_buckets"].append(steps)
        return self._fns[steps]

    # -- execution ----------------------------------------------------------
    def run_microbatch(self, mb: MicroBatch) -> np.ndarray:
        """Run one microbatch; returns (B, H, W, C) samples (incl. padding
        slots — callers drop them via ``mb.valid``)."""
        if mb.batch != self.microbatch:
            raise ValueError(
                f"microbatch has {mb.batch} slots, engine expects "
                f"{self.microbatch}")
        if mb.steps not in self.step_buckets:
            raise ValueError(f"steps {mb.steps} not in configured buckets "
                             f"{self.step_buckets}")
        out = self._fn(mb.steps)(self.params, self._qargs,
                                 jnp.asarray(mb.labels),
                                 jnp.asarray(mb.seeds),
                                 jnp.asarray(mb.guidance))
        return np.asarray(jax.block_until_ready(out))

    def run(self, microbatches: Sequence[MicroBatch]
            ) -> Dict[int, GenResult]:
        """Run microbatches in order; returns {request_id: GenResult}."""
        results: Dict[int, GenResult] = {}
        for mb in microbatches:
            t0 = time.perf_counter()
            samples = self.run_microbatch(mb)
            dt = time.perf_counter() - t0
            for slot, rid in enumerate(mb.request_ids):
                results[rid] = GenResult(
                    request_id=rid, sample=samples[slot], steps=mb.steps,
                    microbatch=mb.batch, wall_s=dt,
                    requested_steps=(mb.requested_steps[slot]
                                     if slot < len(mb.requested_steps)
                                     else None))
            self.stats["microbatches"] += 1
            self.stats["requests"] += mb.n_valid
            self.stats["padded_slots"] += mb.n_padded
            self.stats["wall_s"] += dt
        return results

    def serve(self, requests: Sequence[GenRequest]) -> Dict[int, GenResult]:
        """Convenience: coalesce + run a request list in one call."""
        return self.run(coalesce(requests, self.microbatch,
                                 self.step_buckets))


class AsyncServeEngine:
    """Continuous-batching engine: a slot pool advanced ``chunk`` steps per
    compiled dispatch, with a full request-lifecycle robustness layer.

    Where :class:`ServeEngine` buckets requests by step count and runs each
    bucket's whole chain in one blocking call, this engine keeps a pool of
    ``microbatch`` in-flight slots, each carrying its own
    ``(pos, bucket, label, seed, guidance)`` state, and every dispatch
    advances ALL active slots ``chunk`` denoising steps — requests at
    different timesteps, even different step buckets, share ONE compiled
    executable (TGQ resolves the timestep group as a traced scalar inside
    the kernels; see ``ddpm_chunk_slots``). Finished slots are swapped out
    and queued requests admitted at the next chunk boundary, so a 25-step
    request never waits for a 100-step neighbour to drain.

    Robustness layer (``repro.serving.lifecycle`` / ``.faults``):

    - bounded-queue admission: ``submit`` rejects with a structured
      ``queue_full`` / ``bad_label`` outcome instead of dropping;
    - per-request deadlines + ``cancel``: checked at chunk boundaries, the
      slot is freed and the request ends ``CANCELLED`` (a request that
      FINISHES by the boundary still delivers ``OK``);
    - NaN/Inf quarantine: a post-chunk on-device finiteness guard flags
      only the poisoned slot; it is reset and retried with the SAME
      ``fold_in(PRNGKey(seed), step)`` keys — bit-identical on success —
      and ends ``FAILED`` with a ``nan_poisoned`` error after
      ``max_retries``;
    - degradation ladder on dispatch faults: flash attn -> composed
      kernels -> fake-quant, each step logged; ladder exhausted =>
      every live request fails structured and :class:`EngineFault` raises.

    Scale-out: with ``mesh`` the slot pool is SHARDED across the
    data-parallel mesh exactly like the sync path's microbatches — the
    chunk executable runs under shard_map with params replicated and
    every per-slot array on ``request_spec``, so slot ``s`` lives on
    device ``s // (microbatch/dp)`` and admission into a slot is
    admission onto that device's shard. The batched vector-tgroup
    forward (``ddpm_chunk_slots``) has no cross-slot communication, so
    each device runs the same executable a single-device pool would —
    samples stay bit-identical. ``pipeline >= 2`` adds dispatch-ahead:
    the next chunk is enqueued on the current chunk's device-resident
    outputs BEFORE the host blocks on the small (B,) position/bad reads,
    keeping the device busy while the host resolves the boundary;
    the speculative chunk is drained whenever the boundary mutates slot
    state (admission, completion, cancel/deadline, quarantine reset,
    degradation), so the lifecycle state machine and the NaN-retry
    bit-identity contract are byte-for-byte those of ``pipeline=1``.

    Slot state lives on device; per-chunk host traffic is two (B,)
    arrays (positions + bad flags) — the full latent is pulled once per
    request, at completion. ``clock`` is injectable
    (``faults.FakeClock``) so deadline tests never sleep.

    Tracing: each pump opens ``jax.profiler.TraceAnnotation`` spans,
    ``engine.pump`` around ``engine.admit``, ``engine.dispatch`` (with
    ``engine.compile`` and the blocking reads, ``engine.wait``) and
    ``engine.resolve`` (with ``engine.pull``, tagged ``request_id``).
    They land in a ``jax.profiler`` trace on the device's clock; with no
    trace running each is an inactive TraceMe. ``stats`` counts
    ``chunk_runs`` (every call of the chunk executable), ``drained``
    (dispatch-ahead chunks run and thrown away) and ``live_slot_steps``
    (steps the consumed chunks advanced for live, unpoisoned requests),
    and keeps ``[total_s, longest_s]`` per phase in ``phase_s``.
    """

    # a freed slot parks at pos >= every bucket length: bucket 0, pos n_max
    def __init__(self, params, dcfg: DiTCfg, dif: DiffusionCfg,
                 sched=None, *, ctx=None, mesh: Optional[Mesh] = None,
                 microbatch: int = 4,
                 step_buckets: Sequence[int] = DEFAULT_STEP_BUCKETS,
                 chunk: int = 4, pipeline: int = 2, max_queue: int = 64,
                 max_retries: int = 2,
                 deadline_s: Optional[float] = None, clock=time.monotonic,
                 injector=None, clip_x0: Optional[float] = None):
        self.dcfg = dcfg
        self.dif = dif
        self.sched = sched if sched is not None else make_schedule(dif)
        self.ctx = ctx if ctx is not None else FPContext()
        self.mesh = mesh
        self.microbatch = int(microbatch)
        self.step_buckets = tuple(sorted(int(b) for b in step_buckets))
        self.chunk = int(chunk)
        self.pipeline = max(1, int(pipeline))
        self.max_queue = int(max_queue)
        self.max_retries = int(max_retries)
        self.deadline_s = deadline_s
        self.clip_x0 = clip_x0
        self._clock = clock
        self._injector = injector
        if mesh is not None:
            nd = dp_size(mesh)
            if self.microbatch % nd != 0:
                raise ValueError(
                    f"microbatch {self.microbatch} not divisible by the "
                    f"mesh's {nd} data-parallel shards — each device needs "
                    "an equal, fixed-shape slice of the slot pool")
            params = jax.device_put(params, replicated(mesh))
        self.params = params

        self._slot_sched = make_slot_schedule(dif, self.sched,
                                              self.step_buckets)
        self._n_of = np.asarray(self._slot_sched["n_of"])
        self._n_max = int(self._n_of.max())
        self._bucket_idx = {b: i for i, b in
                            enumerate(self._slot_sched["buckets"])}
        B = self.microbatch
        sshape = (dcfg.img_size, dcfg.img_size, dcfg.in_ch)
        self._x = self._on_pool(jnp.zeros((B,) + sshape, jnp.float32))
        self._pos = self._on_pool(jnp.full((B,), self._n_max, jnp.int32))
        self._bk = self._on_pool(jnp.zeros((B,), jnp.int32))  # ^ all free
        self._y = self._on_pool(jnp.zeros((B,), jnp.int32))
        self._seeds = self._on_pool(jnp.zeros((B,), jnp.uint32))
        self._gs = self._on_pool(jnp.ones((B,), jnp.float32))

        self._slot_rid: List[Optional[int]] = [None] * B
        self._pos_host = np.full((B,), self._n_max, np.int64)
        self.queue: deque = deque()                  # request ids, FIFO
        self.records: Dict[int, lc.RequestRecord] = {}
        self.outcomes: Dict[int, lc.RequestOutcome] = {}
        self._next_id = 0
        self._warned_roundings: set = set()
        self._t0 = clock()

        self.stats: Dict[str, Any] = {
            "dispatches": 0, "chunk_traces": 0, "compile_s": 0.0,
            "degradations": [], "admitted": 0, "rejected": 0, "retries": 0,
            "queue_peak": 0, "chunk_runs": 0, "drained": 0,
            "live_slot_steps": 0, "phase_s": {p: [0.0, 0.0] for p in PHASES},
        }
        self._pending = None            # dispatch-ahead in-flight chunk
        self._chunk_fn = self._build_chunk()
        self._chunk_exec = None         # compiled by _compile_chunk
        self._init_fn = jax.jit(
            lambda seed, n: ddpm_init_latent(seed, n, sshape))

    @classmethod
    def from_artifact(cls, params, artifact, *, kernel=None,
                      attn_impl: Optional[str] = None, sched=None,
                      **kw) -> "AsyncServeEngine":
        """Async engine from a ``QuantArtifact`` (same identity guards as
        ``ServeEngine.from_artifact``)."""
        artifact.check_params(params)
        return cls(params, artifact.model_cfg(), artifact.dif_cfg(), sched,
                   ctx=artifact.context(kernel=kernel, attn_impl=attn_impl),
                   **kw)

    def _on_pool(self, a):
        """Place per-slot state where the chunk executable keeps it: with a
        mesh, sharded on the DP axis (slot ``s`` on device
        ``s // (microbatch / dp)``), the layout the executable returns, so
        every dispatch sees one input sharding and compiles once."""
        a = jnp.asarray(a)
        if self.mesh is None:
            return a
        return jax.device_put(a, NamedSharding(
            self.mesh, batch_spec(self.mesh, a.ndim)))

    # -- executable construction -------------------------------------------
    def _build_chunk(self):
        dcfg, dif, S = self.dcfg, self.dif, self._slot_sched
        clip, chunk = self.clip_x0, self.chunk
        null_label = dcfg.n_classes
        stats = self.stats
        self._qargs, rebuild = _ctx_arrays(self.ctx, self.mesh)

        def run(params, qargs, x, pos, bk, y, seeds, gs):
            stats["chunk_traces"] += 1      # python side effect: counts
            eps = lambda xx, t, yy, c: dit_apply(   # TRACES, not dispatches
                params, dcfg, xx, t, yy, ctx=c)
            return ddpm_chunk_slots(eps, dif, S, x, pos, bk, y, seeds, gs,
                                    null_label=null_label, chunk=chunk,
                                    ctx=rebuild(qargs), clip_x0=clip)

        if self.mesh is not None:
            rspec = request_spec(self.mesh)
            run = jax.shard_map(run, mesh=self.mesh,
                                in_specs=(P(), P(), batch_spec(self.mesh, 4),
                                          rspec, rspec, rspec, rspec, rspec),
                                out_specs=(batch_spec(self.mesh, 4), rspec,
                                           rspec),
                                check_vma=False)
        return jax.jit(run)

    def _compile_chunk(self) -> None:
        """Trace and compile the chunk executable for the pool's shapes.

        This runs outside the degradation ladder: an executable that
        cannot be traced or compiled (a kernel the backend's compiler
        refuses, a shape error) is a build fault, and stepping down to a
        slower, simulated rung would hide it. The dispatch that follows
        calls this compiled executable, so no compile happens inside the
        ladder."""
        t0 = time.perf_counter()
        with self._span("compile"):
            self._chunk_exec = self._chunk_fn.lower(
                *self._chunk_args(self._x, self._pos)).compile()
        self.stats["compile_s"] += time.perf_counter() - t0

    def _chunk_args(self, x, pos):
        return (self.params, self._qargs, x, pos, self._bk, self._y,
                self._seeds, self._gs)

    def _run_chunk(self, x, pos):
        """Enqueue one run of the compiled chunk executable on ``(x,
        pos)`` and the pool's other slot state."""
        out = self._chunk_exec(*self._chunk_args(x, pos))
        self.stats["chunk_runs"] += 1
        return out

    @contextlib.contextmanager
    def _span(self, phase: str, **tags):
        """``engine.<phase>`` as a profiler span (``tags`` become its
        metadata); a phase in ``PHASES`` also adds its seconds to
        ``stats["phase_s"][phase]`` as ``[total_s, longest_s]``."""
        acc = self.stats["phase_s"].get(phase)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"engine.{phase}", **tags):
                yield
        finally:
            if acc is not None:
                dt = time.perf_counter() - t0
                acc[0] += dt
                acc[1] = max(acc[1], dt)

    # -- admission ----------------------------------------------------------
    def _reject(self, req: GenRequest, code: str, message: str) -> int:
        now = self._clock()
        rec = lc.RequestRecord(request=req, status=lc.REJECTED,
                               submit_ts=now, finish_ts=now,
                               error=lc.FaultInfo(code=code, message=message))
        self.records[req.request_id] = rec
        self.outcomes[req.request_id] = lc.outcome_of(rec, None, now)
        self.stats["rejected"] += 1
        return req.request_id

    def submit_request(self, req: GenRequest) -> int:
        """Admission control for a pre-built request: validates the label,
        applies bounded-queue backpressure, and either queues the request
        or records a structured ``REJECTED`` outcome (never raises, never
        drops silently). Returns the request id either way."""
        rid = req.request_id
        if rid in self.records:
            raise ValueError(f"duplicate request id {rid}")
        try:
            validate_label(req.label, self.dcfg.n_classes, rid)
        except ValueError as e:
            return self._reject(req, lc.BAD_LABEL, str(e))
        if len(self.queue) >= self.max_queue:
            return self._reject(
                req, lc.QUEUE_FULL,
                f"request {rid}: queue full ({self.max_queue} waiting) — "
                "retry with backoff")
        now = self._clock()
        dl = req.deadline_s if req.deadline_s is not None else self.deadline_s
        rec = lc.RequestRecord(
            request=req, submit_ts=now,
            deadline_ts=(now + dl) if dl is not None else None)
        rec.log(now, "queued")
        self.records[rid] = rec
        self.queue.append(rid)
        self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                       len(self.queue))
        return rid

    def submit(self, label: int, steps: int = 50, cfg_scale: float = 1.0,
               seed: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Build + submit one request; returns its id. Check
        ``outcomes[rid]`` for an immediate structured rejection."""
        rid = self._next_id
        self._next_id += 1
        bucketed = bucket_steps(steps, self.step_buckets)
        if bucketed != int(steps) and int(steps) not in self._warned_roundings:
            self._warned_roundings.add(int(steps))
            warnings.warn(
                f"requested {int(steps)} sampler steps rounded to bucket "
                f"{bucketed} (step_buckets={self.step_buckets}); "
                "RequestOutcome.requested_steps records the original ask",
                stacklevel=2)
        return self.submit_request(GenRequest(
            request_id=rid, label=int(label), steps=bucketed,
            cfg_scale=float(cfg_scale),
            seed=int(seed) if seed is not None else rid,
            requested_steps=int(steps), deadline_s=deadline_s))

    def cancel(self, rid: int) -> bool:
        """Request cancellation. Queued requests resolve at admission;
        running ones free their slot at the next chunk boundary. Returns
        False if the request is already terminal."""
        rec = self.records.get(rid)
        if rec is None or rec.status in lc.TERMINAL:
            return False
        rec.cancel_requested = True
        return True

    # -- slot management ----------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [s for s, rid in enumerate(self._slot_rid) if rid is None]

    def _place(self, slot: int, rec: lc.RequestRecord) -> None:
        self._drain_pipeline()          # pool mutates: in-flight chunk stale
        req = rec.request
        bi = self._bucket_idx[bucket_steps(req.steps, self.step_buckets)]
        n = int(self._n_of[bi])
        x0 = self._init_fn(jnp.uint32(req.seed), jnp.int32(n))
        self._x = self._x.at[slot].set(x0)
        self._pos = self._pos.at[slot].set(0)
        self._bk = self._bk.at[slot].set(bi)
        self._y = self._y.at[slot].set(req.label)
        self._seeds = self._seeds.at[slot].set(jnp.uint32(req.seed))
        self._gs = self._gs.at[slot].set(req.cfg_scale)
        self._slot_rid[slot] = req.request_id
        self._pos_host[slot] = 0
        rec.slot = slot
        if rec.admit_ts is None:       # retries keep the original admit time
            rec.admit_ts = self._clock()
            self.stats["admitted"] += 1
        rec.status = lc.RUNNING
        rec.log(self._clock(), f"slot {slot}")

    def _release(self, slot: int) -> None:
        self._drain_pipeline()          # pool mutates: in-flight chunk stale
        self._x = self._x.at[slot].set(0.0)   # clear poison from the pool
        self._pos = self._pos.at[slot].set(self._n_max)
        self._bk = self._bk.at[slot].set(0)
        self._slot_rid[slot] = None
        self._pos_host[slot] = self._n_max

    def _finish(self, rec: lc.RequestRecord, status: str,
                sample: Optional[np.ndarray],
                error: Optional[lc.FaultInfo] = None) -> None:
        now = self._clock()
        rec.status = status
        rec.error = error
        rec.finish_ts = now
        rec.log(now, status)
        if rec.slot is not None:
            self._release(rec.slot)
            rec.slot = None
        self.outcomes[rec.request.request_id] = lc.outcome_of(
            rec, sample, now)

    def _admit(self) -> None:
        free = self._free_slots()
        while free and self.queue:
            rid = self.queue.popleft()
            rec = self.records[rid]
            now = self._clock()
            if rec.cancel_requested:
                self._finish(rec, lc.CANCELLED, None, lc.FaultInfo(
                    code=lc.CANCELLED_BY_USER,
                    message=f"request {rid} cancelled while queued"))
                continue
            if rec.deadline_ts is not None and now > rec.deadline_ts:
                self._finish(rec, lc.CANCELLED, None, lc.FaultInfo(
                    code=lc.DEADLINE,
                    message=f"request {rid} deadline passed after "
                            f"{now - rec.submit_ts:.3f}s in queue"))
                continue
            self._place(free.pop(0), rec)

    # -- the pump ------------------------------------------------------------
    @property
    def active(self) -> int:
        return sum(1 for r in self._slot_rid if r is not None)

    def _fail_all_live(self, error: lc.FaultInfo) -> None:
        for rid in list(self.queue):
            self._finish(self.records[rid], lc.FAILED, None, error)
        self.queue.clear()
        for slot, rid in enumerate(self._slot_rid):
            if rid is not None:
                self._finish(self.records[rid], lc.FAILED, None, error)

    def _drain_pipeline(self) -> None:
        """Discard any dispatch-ahead chunk: its inputs no longer match
        the slot pool (admission, release, quarantine reset, or a
        degradation rebuilt the executable). The device runs a dropped
        chunk all the same; ``stats["drained"]`` counts them."""
        if self._pending is not None:
            self.stats["drained"] += 1
            self._pending = None

    def _dispatch(self):
        """One chunk dispatch with the degradation ladder and dispatch-ahead
        pipelining. A freshly built executable is compiled first, outside
        the ladder (``_compile_chunk``), so build faults raise. Slot state
        is only replaced AFTER the blocking reads succeed, so a failed
        dispatch (device fault, injected) is side-effect free and the same
        chunk can be retried on a degraded context. With ``pipeline >= 2``
        the NEXT chunk is enqueued on this chunk's device-resident outputs
        BEFORE the host blocks on the small (B,) reads — two dispatches in
        flight, host boundary work overlapped with device compute. The
        speculative chunk is only consumed if this
        boundary mutates no slot state; every mutating path drains it
        (``_drain_pipeline``), so fault/deadline/quarantine semantics are
        exactly those of ``pipeline=1``."""
        while True:
            if self._chunk_exec is None:
                self._compile_chunk()
            self.stats["dispatches"] += 1
            try:
                if self._injector is not None:
                    self._injector.before_dispatch(self.stats["dispatches"])
                if self._pending is not None:
                    x, pos, bad = self._pending
                    self._pending = None
                else:
                    x, pos, bad = self._run_chunk(self._x, self._pos)
                if self.pipeline >= 2:
                    # dispatch-ahead: enqueue the next chunk on the async
                    # dispatch queue now; pump() drains it if this chunk's
                    # boundary mutates any slot
                    self._pending = self._run_chunk(x, pos)
                # block on the SMALL outputs only; x stays device-resident
                with self._span("wait"):
                    pos_h = np.array(pos)  # writable copy: retries reset it
                    bad_h = np.array(bad)
                return x, pos_h, bad_h
            except Exception as e:            # noqa: BLE001 — ladder seam
                self._drain_pipeline()
                down = degrade_context(self.ctx)
                if down is None:
                    err = lc.FaultInfo(
                        code=lc.ENGINE_FAULT,
                        message=f"dispatch failed with no degradation rung "
                                f"left: {type(e).__name__}: {e}")
                    self._fail_all_live(err)
                    raise EngineFault(err.message) from e
                self.ctx, reason = down
                self.stats["degradations"].append(
                    {"reason": reason, "error": f"{type(e).__name__}: {e}"})
                self._chunk_fn = self._build_chunk()
                self._chunk_exec = None

    def pump(self) -> bool:
        """One engine cycle: admit -> dispatch one chunk -> resolve slots.
        Returns False when there was nothing to do (pool empty and queue
        empty after admission)."""
        with self._span("pump"):
            with self._span("admit"):
                self._admit()
            if self.active == 0:
                return False
            with self._span("dispatch"):
                x, pos_h, bad_h = self._dispatch()
            with self._span("resolve"):
                self._resolve(x, pos_h, bad_h)
            return True

    def _resolve(self, x, pos_h, bad_h) -> None:
        """The chunk boundary: finish, cancel or quarantine each live
        slot from the chunk's outputs, then take them as the pool's state."""
        didx = self.stats["dispatches"]
        now = self._clock()

        for slot, rid in enumerate(self._slot_rid):
            if rid is None:
                continue
            rec = self.records[rid]
            n = int(self._n_of[self._bucket_idx[
                bucket_steps(rec.request.steps, self.step_buckets)]])
            p_before, p_after = int(self._pos_host[slot]), int(pos_h[slot])
            poisoned = bool(bad_h[slot])
            fault = None
            if self._injector is not None:
                fault = self._injector.poison(didx, rid, p_before, p_after)
                if fault is not None:
                    x = x.at[slot].set(jnp.nan)   # poison ONLY this slot
                    poisoned = True
            if poisoned:
                step = fault.at_step if fault is not None else p_before
                code = (lc.SLOT_ERROR if fault is not None
                        and fault.kind == "slot_error" else lc.NAN_POISONED)
                if rec.retries >= self.max_retries:
                    self._x = x   # keep the pool consistent before release
                    self._finish(rec, lc.FAILED, None, lc.FaultInfo(
                        code=code, step=step, retries=rec.retries,
                        message=f"request {rid}: non-finite latent at scan "
                                f"position ~{step}; gave up after "
                                f"{rec.retries} retries"))
                    x = self._x
                    continue
                # quarantine: reset THIS slot to scan position 0 with the
                # same fold_in(PRNGKey(seed), i) keys — the retry replays
                # the identical trajectory, bit-identical on success
                rec.retries += 1
                self.stats["retries"] += 1
                rec.log(now, f"quarantined@{step} retry {rec.retries}")
                self._drain_pipeline()  # slot resets: in-flight chunk stale
                x = x.at[slot].set(self._init_fn(
                    jnp.uint32(rec.request.seed), jnp.int32(n)))
                pos_h[slot] = 0
                continue
            self.stats["live_slot_steps"] += min(p_after, n) - p_before
            if p_after >= n:                      # finished: the ONE place
                self._x = x                       # the full latent leaves
                with self._span("pull", request_id=rid):  # the device
                    sample = np.asarray(self._x[slot])
                self._finish(rec, lc.OK, sample)
                x = self._x
                continue
            if rec.cancel_requested:
                self._x = x
                self._finish(rec, lc.CANCELLED, None, lc.FaultInfo(
                    code=lc.CANCELLED_BY_USER, step=p_after,
                    message=f"request {rid} cancelled at chunk boundary"))
                x = self._x
                continue
            if rec.deadline_ts is not None and now > rec.deadline_ts:
                self._x = x
                self._finish(rec, lc.CANCELLED, None, lc.FaultInfo(
                    code=lc.DEADLINE, step=p_after,
                    message=f"request {rid}: deadline exceeded at chunk "
                            f"boundary (scan position {p_after}/{n})"))
                x = self._x
                continue

        self._x = x
        self._pos = self._on_pool(pos_h.astype(np.int32))
        for slot, rid in enumerate(self._slot_rid):
            if rid is not None:
                self._pos_host[slot] = int(pos_h[slot])

    def run_until_drained(self, max_pumps: int = 100_000
                          ) -> Dict[int, lc.RequestOutcome]:
        """Pump until every submitted request is terminal."""
        pumps = 0
        while self.queue or self.active:
            if not self.pump():
                break
            pumps += 1
            if pumps > max_pumps:
                raise EngineFault(
                    f"async loop did not drain within {max_pumps} pumps — "
                    f"{self.active} slots active, {len(self.queue)} queued")
        return self.outcomes

    def serve(self, requests: Sequence[GenRequest]
              ) -> Dict[int, lc.RequestOutcome]:
        """Submit pre-built requests (keeping their ids) and drain."""
        for r in requests:
            self.submit_request(r)
        return self.run_until_drained()

    def metrics(self) -> Dict[str, Any]:
        """Lifecycle metrics over everything terminal so far."""
        wall = self._clock() - self._t0
        return lc.summarize(list(self.outcomes.values()), wall)
