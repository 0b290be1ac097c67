"""One run of one cell: set-up, warm-up, the measured window, the
comparison with the reference, and the result.

The window drives the served path as a deployment does:
``AsyncServeEngine.from_artifact(params, artifact, ...)`` fed through
``submit_request`` and advanced by ``pump``. The benchmark keeps its own
books from outside the engine: when each request was due, submitted,
admitted (the start of the pump after which it left the queue) and done
(the sample on the host), and which requests each pump advanced.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from harness import peaks, reference, trace, traffic, weights

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(BENCH, ".cache")
clock = time.monotonic
TRACE_S = 12.0            # traced part of a --trace 1 window, seconds


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Rec:
    req: traffic.Req
    due: float
    submit: float
    admit: Optional[float] = None
    done: Optional[float] = None
    status: str = "QUEUED"
    steps_done: int = 0
    steps_window: int = 0
    sample: Optional[np.ndarray] = None


@dataclasses.dataclass
class Pump:
    start: float
    end: float
    dispatched: int           # requests the pump advanced
    steps: int                # denoising steps it completed, all slots
    in_window: bool
    traced: bool


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: dict
    config: dict
    traffic: dict
    seconds: float
    seed: int
    chips: int
    device_kind: str
    peaks: dict
    t0: float = 0.0
    t1: float = 0.0
    setup_s: float = 0.0
    requests: List[Rec] = dataclasses.field(default_factory=list)
    pumps: List[Pump] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def slots(self) -> int:
        return int(self.config["engine"]["slots"])

    @property
    def rows(self) -> int:
        """Rows of every forward: both CFG halves of every slot."""
        return 2 * self.slots

    @property
    def act_bytes(self) -> int:
        return int(np.dtype(self.config["dtype"]).itemsize)

    def window_requests(self) -> List[Rec]:
        """Open loop: the requests due in the window. Closed loop: the
        requests the window advanced."""
        if self.traffic["loop"] == "open":
            return [r for r in self.requests
                    if self.t0 <= r.due < self.t0 + self.seconds]
        return [r for r in self.requests if r.steps_window > 0]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def model_cfg(c: dict):
    from repro.models.dit import DiTCfg
    return DiTCfg(img_size=c["input_size"], in_ch=c["in_channels"],
                  patch=c["patch_size"], d_model=c["hidden_size"],
                  n_layers=c["depth"], n_heads=c["num_heads"],
                  mlp_ratio=c["mlp_ratio"], n_classes=c["num_classes"],
                  dtype=c["dtype"], class_dropout=c["class_dropout_prob"])


def artifact(params, c: dict):
    """The configuration's ``QuantArtifact``, as a deployment serves it:
    ``QuantArtifact.load`` from the benchmark's cache when a run in this
    checkout saved it, else made by ``quantize()`` and saved there (once:
    a saved artifact that does not load is kept, not written again).
    Keyed by the model, the weights seed and the recipe."""
    from repro.diffusion import DiffusionCfg
    from repro.quant import QuantArtifact, QuantRecipe, quantize
    recipe = QuantRecipe(**c["quant"])
    key = json.dumps({k: c[k] for k in sorted(c) if k not in
                      ("name", "source", "engine", "reference", "limits",
                       "assumed", "notes", "reduced", "clip_denoised")},
                     sort_keys=True)
    key = hashlib.sha256((key + recipe.canonical_json()).encode()
                         ).hexdigest()[:16]
    path = os.path.join(CACHE, "artifacts", f"{c['name']}-{key}")
    saved = os.path.exists(os.path.join(path, "artifact.json"))
    if saved:
        try:
            return QuantArtifact.load(path, expect_recipe=recipe,
                                      params=params), "loaded"
        except Exception as e:            # noqa: BLE001 - quantized below
            log(f"saved artifact does not load ({type(e).__name__}: {e}); "
                "quantizing")
    art = quantize(params, model_cfg(c),
                   DiffusionCfg(tgq_groups=c["tgq_groups"]), recipe,
                   provenance={"weights": f"benchmark, seed "
                                          f"{c['weights_seed']}"})
    if saved:
        return art, "quantized"
    part = path + ".partial"
    shutil.rmtree(part, ignore_errors=True)
    art.save(part)
    shutil.rmtree(path, ignore_errors=True)
    os.replace(part, path)
    return art, "quantized"


def clip_x0(c: dict) -> Optional[float]:
    """The sampler's clip of its x0 prediction: to the data range [-1, 1]
    where the configuration sets ``clip_denoised``, else none."""
    return 1.0 if c.get("clip_denoised") else None


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------
class Feeder:
    """Feeds the engine and keeps the books."""

    def __init__(self, eng, stream, chunk: int):
        self.eng, self.stream, self.chunk = eng, stream, chunk
        self.live: Dict[int, Rec] = {}
        self.all: List[Rec] = []
        self.pumps: List[Pump] = []
        self.completed = 0
        self.tracing = False

    def submit(self, due: Optional[float] = None) -> Rec:
        from repro.serving import GenRequest
        r = next(self.stream)
        now = clock()
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.eng.submit_request(GenRequest(
                request_id=r.index, label=r.label, steps=r.steps,
                cfg_scale=r.guidance, seed=r.noise_seed))
        rec = Rec(req=r, due=now if due is None else due, submit=now)
        self.live[r.index] = rec
        self.all.append(rec)
        return rec

    @property
    def queued(self) -> int:
        return sum(1 for r in self.live.values() if r.admit is None)

    def pump(self, in_window: bool) -> bool:
        t_a = clock()
        with jax.profiler.TraceAnnotation("bench.pump"):
            worked = self.eng.pump()
        t_b = clock()
        dispatched = steps = 0
        for rid in list(self.live):
            rec = self.live[rid]
            st = self.eng.records[rid].status
            if rec.admit is None and st != "QUEUED":
                rec.admit = t_a
            if worked and st in ("RUNNING", "OK"):
                dispatched += 1
                n = min(self.chunk, rec.req.steps - rec.steps_done)
                rec.steps_done += n
                steps += n
                if in_window:
                    rec.steps_window += n
            rec.status = st
            if st in ("OK", "FAILED", "REJECTED", "CANCELLED"):
                if st == "OK":
                    with jax.profiler.TraceAnnotation("bench.sample_pull"):
                        rec.sample = np.asarray(self.eng.outcomes[rid].sample)
                    self.completed += 1
                rec.done = clock()
                del self.live[rid]
        self.pumps.append(Pump(t_a, t_b, dispatched, steps, in_window,
                               self.tracing))
        return worked


class Profiler:
    """``jax.profiler`` over part of the window, written under TMPDIR
    and read back when it stops."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.on = False

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self) -> trace.Trace:
        jax.profiler.stop_trace()
        self.on = False
        try:
            return trace.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def closed_loop(drv: Feeder, run: Run, prof: Optional[Profiler]):
    """Warm-up, then the window, keeping the queue one pool deep. The
    warm-up fills the pool one request at a time, so that completions
    spread over the window instead of arriving in lockstep."""
    slots = run.slots
    per_req = math.ceil(run.traffic["steps"] / drv.chunk)

    def top_up():
        while drv.queued < slots:
            drv.submit()

    for _ in range(slots):
        drv.submit()
        for _ in range(max(1, per_req // slots)):
            drv.pump(False)
    while drv.completed == 0:
        top_up()
        drv.pump(False)
    top_up()
    drv.pump(False)                 # the first freed slot is refilled
    window(drv, run, prof, top_up)


def open_loop(drv: Feeder, run: Run, prof: Optional[Profiler]):
    """A closed warm-up turn that compiles every path, drained; then
    arrivals from ``lead_s`` before the window to ``wait_s`` after it,
    each submitted when it is due."""
    tr = run.traffic
    for _ in range(run.slots):
        drv.submit()
    while drv.live:
        drv.pump(False)
    lead, wait = float(tr.get("lead_s", 0.0)), float(tr.get("wait_s", 60.0))
    offs = traffic.arrival_offsets(tr, run.seed, run.seconds,
                                   before=lead, after=wait)
    start = clock() + lead
    due = [start + o for o in offs]
    nxt = 0

    def arrivals():
        nonlocal nxt
        now = clock()
        while nxt < len(due) and due[nxt] <= now:
            drv.submit(due=due[nxt])
            nxt += 1

    while clock() < start:
        arrivals()
        if drv.live:
            drv.pump(False)
        else:
            time.sleep(min(0.01, max(0.0, due[nxt] - clock())))
    window(drv, run, prof, arrivals, t0=start)
    # wait for every request due in the window, arrivals continuing
    close = run.t0 + run.seconds
    pending = [r for r in drv.all if run.t0 <= r.due < close]
    while any(r.done is None for r in pending) and clock() < close + wait:
        arrivals()
        if drv.live:
            drv.pump(False)
        else:
            time.sleep(0.005)


def window(drv: Feeder, run: Run, prof: Optional[Profiler], feed,
           t0: Optional[float] = None):
    """Pump for ``run.seconds``; with a profiler, trace its first
    ``TRACE_S`` seconds."""
    if prof is not None:
        prof.start()
        drv.tracing = True
    run.t0 = clock() if t0 is None else t0
    with CompileCounter() as compiles, GcLog() as gcs:
        while clock() - run.t0 < run.seconds:
            feed()
            if drv.live:
                drv.pump(True)
            else:
                time.sleep(0.002)
            if prof is not None and prof.on and \
                    clock() - run.t0 >= min(TRACE_S, run.seconds):
                drv.tracing = False
                run.trace = {"raw": prof.stop()}
        run.t1 = drv.pumps[-1].end if drv.pumps else clock()
    if prof is not None and prof.on:
        drv.tracing = False
        run.trace = {"raw": prof.stop()}
    log(f"compiles inside the window: {compiles.n}")
    log(f"garbage collections inside the window: {gcs.summary(run.t0)}")


class CompileCounter:
    """Counts lowerings (each a compile or a compile-cache read) while the
    ``with`` block runs, through a ``jax.monitoring`` listener."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0

    def _listen(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


class GcLog:
    """The garbage collections made while the ``with`` block runs, through
    ``gc.callbacks``: (generation, start, seconds) of each."""

    def __init__(self):
        self.events: List[tuple] = []
        self._start: Optional[float] = None

    def _listen(self, phase, info):
        if phase == "start":
            self._start = clock()
        elif self._start is not None:
            self.events.append((info["generation"], self._start,
                                clock() - self._start))
            self._start = None

    def __enter__(self):
        gc.callbacks.append(self._listen)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._listen)

    def summary(self, t0: float) -> str:
        """Count per generation, and each collection of 10 ms or more as
        generation@seconds-into-the-window:duration."""
        per = [sum(1 for e in self.events if e[0] == g) for g in range(3)]
        longest = max((e[2] for e in self.events), default=0.0)
        slow = " ".join(f"{g}@{s - t0:.3f}:{d:.4f}"
                        for g, s, d in self.events if d >= 0.01)
        return (f"{len(self.events)} (generations 0/1/2: "
                f"{per[0]}/{per[1]}/{per[2]}), longest {longest:.4f} s"
                + (f"; 10 ms or more: {slow}" if slow else ""))


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def reduce_trace(run: Run) -> dict:
    """The numbers the per-layer readers take from the trace, over the
    traced pumps: from the first traced pump's start to the last one's
    end, on the trace's own clock."""
    raw: trace.Trace = run.trace["raw"]
    pumps = sorted((e for e in raw.host if e[0] == "bench.pump"),
                   key=lambda e: e[1])
    if not pumps or not raw.ops:
        return {}
    t0, t1 = pumps[0][1], pumps[-1][2]
    devs = sorted(raw.ops)
    busy = [trace.busy_ns(raw.ops[d], t0, t1) for d in devs]
    idle = trace.gaps(raw.ops[devs[0]], t0, t1)
    by_host = trace.attribute(idle, raw.host)
    kinds = trace.kind_time(raw.ops[devs[0]], t0, t1)
    mods: Dict[str, int] = {}
    durs: Dict[str, int] = {}
    for n, s, e in trace.clip(raw.modules.get(devs[0], []), t0, t1):
        mods[n] = mods.get(n, 0) + 1
        durs[n] = durs.get(n, 0) + (e - s)
    chunk_mod = max(durs, key=durs.get) if durs else None
    traced = [p for p in run.pumps if p.traced]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": float(np.mean(busy)) / 1e9,
        "idle_by_host_s": {k: v / 1e9 for k, v in by_host.items()},
        "kinds": {k: (c, d / 1e9) for k, (c, d) in kinds.items()},
        "n_pumps": len(pumps),
        "pumps_dispatching": sum(1 for p in traced if p.dispatched),
        "steps": sum(p.steps for p in traced),
        "chunk_runs": mods.get(chunk_mod, 0) if chunk_mod else 0,
        "device_ops": trace.top_ops(raw.ops[devs[0]], t0, t1),
        "idle_gaps": sorted(((k, v / 1e9) for k, v in by_host.items()),
                            key=lambda kv: -kv[1])[:10],
    }


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def compare(run: Run, n_max: int, control: Optional[int] = None) -> dict:
    """Samples of finished window requests against the reference.

    A sample of at most ``n_max`` of the requests the window finished,
    drawn from the seed after the longest have been taken; the reference
    runs the same requests (labels, noise seeds, guidance, steps) from the
    same weights, and each is judged by the relative L2 distance of its
    sample from the reference's. With ``control``, the reference at that
    many bits supplies the samples judged instead of the program."""
    done = [r for r in run.window_requests()
            if r.status == "OK" and r.sample is not None]
    if not done:
        return {"n": 0, "rel_l2": [], "program": []}
    rng = np.random.default_rng([int(run.seed), 3])
    longest = max(r.req.steps for r in done)
    order = sorted(range(len(done)), key=lambda i: (
        done[i].req.steps != longest, rng.random()))
    pick = [done[i] for i in order[:n_max]]
    c = run.config
    w = weights.make(c)
    prec = c["reference"]["precision"]
    block = int(c["reference"]["block"])
    clip = clip_x0(c)
    out, program = [], []
    for steps in sorted({r.req.steps for r in pick}):
        f = reference.sampler(c, steps, prec, clip_x0=clip)
        fc = (reference.sampler(c, steps, prec, control, clip) if control
              else None)
        todo = [r for r in pick if r.req.steps == steps]
        for i in range(0, len(todo), block):
            part = todo[i:i + block]
            args = (w, [r.req.label for r in part],
                    [r.req.noise_seed for r in part],
                    [r.req.guidance for r in part])
            ref = np.asarray(f(*args), np.float64)
            prog = np.stack([r.sample for r in part]).astype(np.float64)
            judged = prog if fc is None else np.asarray(fc(*args),
                                                        np.float64)
            program += [rel_l2(s, x) for s, x in zip(prog, ref)]
            out += [rel_l2(s, x) for s, x in zip(judged, ref)]
    return {"n": len(out), "rel_l2": out, "program": program}


def rel_l2(s: np.ndarray, x: np.ndarray) -> float:
    d = float(np.linalg.norm(s - x) / np.linalg.norm(x))
    return d if np.isfinite(d) else float("inf")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def load_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    import importlib.util
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def run_cell(bench: dict, cell: dict, config: dict, tr: dict, seed: int,
             seconds: float, traced: bool, t_start: float,
             control: Optional[int] = None) -> dict:
    """Set up, warm up, measure, compare; returns the result line's
    object. With ``control`` (a bit width), the reference fake-quantized
    at that width takes the program's place in the comparison: the
    lower-precision control, which has to come out not correct."""
    from repro.serving import AsyncServeEngine

    devs = jax.devices()
    kind = devs[0].device_kind
    run = Run(cell=cell, config=config, traffic=tr, seconds=seconds,
              seed=seed, chips=int(cell["chips"]), device_kind=kind,
              peaks=peaks.peaks(kind) if devs[0].platform == "tpu" else {})
    e = config["engine"]

    t = clock()
    params = weights.make(config)
    jax.block_until_ready(params)
    log(f"set-up: weights {clock() - t:.3f} s")
    t = clock()
    art, how = artifact(params, config)
    log(f"set-up: artifact {how} in {clock() - t:.3f} s: {art.summary()}")
    t = clock()
    eng = AsyncServeEngine.from_artifact(
        params, art, microbatch=int(e["slots"]),
        step_buckets=tuple(e["step_buckets"]), chunk=int(e["chunk"]),
        pipeline=int(e["pipeline"]), clip_x0=clip_x0(config),
        max_queue=1 << 30)
    log(f"set-up: engine {clock() - t:.3f} s")

    stream = traffic.stream(tr, config["num_classes"], seed)
    drv = Feeder(eng, stream, int(e["chunk"]))
    prof = Profiler() if traced else None
    t = clock()
    (open_loop if tr["loop"] == "open" else closed_loop)(drv, run, prof)
    run.setup_s = run.t0 - t_start
    run.requests, run.pumps = drv.all, drv.pumps
    log(f"set-up: warm-up {run.t0 - t:.3f} s "
        f"(engine compile {eng.stats.get('compile_s', 0.0):.3f} s); "
        f"set-up {run.setup_s:.3f} s; window {run.window_s:.3f} s, "
        f"{sum(1 for p in run.pumps if p.in_window)} pumps")
    win = [p for p in run.pumps if p.in_window]
    log("window pumps (ms:requests advanced): " + " ".join(
        f"{round(1000 * (p.end - p.start))}:{p.dispatched}" for p in win))
    if eng.stats.get("degradations"):
        log(f"engine degraded: {eng.stats['degradations']}")

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    if run.trace is not None:
        run.trace = reduce_trace(run)

    # free the program's state before the reference takes the chip
    del eng, art, params, drv
    gc.collect()
    t = clock()
    cmp = compare(run, int(config["reference"]["requests"]), control)
    log(f"reference: {cmp['n']} requests in {clock() - t:.3f} s; program's "
        f"relative L2 per request {cmp['program']}")
    if control:
        log(f"control ({control} bits): relative L2 per request "
            f"{cmp['rel_l2']}")

    limit = float(config["limits"]["sample_rel_l2"])
    worst = max(cmp["rel_l2"]) if cmp["rel_l2"] else float("inf")
    correct = cmp["n"] > 0 and worst <= limit

    wreq = run.window_requests()
    failed = sum(1 for r in wreq if r.status != "OK" and not (
        r.status in ("RUNNING", "QUEUED") and run.traffic["loop"] == "closed"))
    key = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in bench[key]:
        if not applies(m, cell["name"]):
            continue
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(wreq),
           "failed": failed, "metrics": metrics, "device": device}
    if traced and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    shown = worst if math.isfinite(worst) else 1e30
    out["checks"] = {"sample_rel_l2": {"value": shown, "limit": limit,
                                       "requests": cmp["n"]}}
    return out
