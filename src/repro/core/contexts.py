"""OpContext implementations for the PTQ engine.

Pipeline (Algorithm 1):
  1. ``RecordingContext``    — one FP forward; discovers every quantizable
     op, its einsum spec, shapes, and input PROVENANCE (whether operand A
     is a marked post-softmax / post-GELU / post-SiLU tensor).
  2. ``CalibrationContext``  — eager FP forwards over the calibration set;
     stores (batch-subsampled) operand tensors per op, tagged with the
     TGQ timestep group. The range pipeline's ``RangeCaptureContext``
     keeps only statistics instead, reduced on the device.
  3. ``TapContext``          — jitted forward with additive zero "taps" on
     every op output; ``jax.grad`` w.r.t. the taps yields exactly
     dL/dz^(l), the Fisher weights of Hessian-guided optimization.
  4. ``QuantContext``        — applies the calibrated quantizers
     (simulated quant-dequant). ``kernel=True`` routes packed linears
     through the int8/int6/packed-int4 Pallas kernels instead.

Provenance tracking uses tensor identity: ``act(name, x, kind)`` marks
``id(x)`` so the directly-consuming matmul knows its operand is the
specially-distributed tensor the paper treats with MRQ/TGQ. This works
both eagerly (concrete arrays) and under a single trace (tracer ids are
stable within a trace). The mark holds the tensor itself and matches
only that object: an id alone is reused as soon as its array is freed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.ctx import OpContext, apply_gate_residual, apply_norm_mod
from repro.core.quantizers import TGQ, apply_quantizer


# ---------------------------------------------------------------------------
# op registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class OpInfo:
    name: str
    kind: str                    # 'linear' | 'einsum'
    spec: Optional[str] = None   # einsum spec (einsum ops)
    b_is_weight: bool = False    # einsum operand b is a parameter tensor
    a_kind: str = "plain"        # 'plain' | 'post_softmax' | 'post_gelu' | 'post_silu'
    x_shape: tuple = ()
    w_shape: tuple = ()
    out_shape: tuple = ()
    n_calls: int = 0             # calls per forward (shared-name ops)


@dataclasses.dataclass
class RecordingContext(OpContext):
    """Discovers the op graph. Execution is full-precision.

    ``acts`` records every act hook (name -> kind). Hooks whose tensor is
    DIRECTLY consumed by a matmul (post-softmax probs, post-GELU hidden)
    are quantized at the consumer (where the HO objective lives); hooks
    that feed elementwise ops first (SwiGLU's silu gate, multiplied by
    ``up`` before the down-proj) are quantized AT THE HOOK — the paper's
    two-lobe asymmetry exists on the silu output, not on the product.
    """
    registry: Dict[str, OpInfo] = dataclasses.field(default_factory=dict)
    acts: Dict[str, str] = dataclasses.field(default_factory=dict)
    _marks: Dict[int, tuple] = dataclasses.field(default_factory=dict)

    def _reg(self, name, **kw):
        if name in self.registry:
            self.registry[name].n_calls += 1
            return self.registry[name]
        info = OpInfo(name=name, **kw)
        info.n_calls = 1
        self.registry[name] = info
        return info

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        # norm_mod is applied BEFORE registering: the op's quantizable
        # input is the modulated tensor (what the matmul consumes), same
        # as when the model computed the chain itself. The a_kind mark is
        # looked up on the ORIGINAL tensor — fusion sites with norm_mod
        # have plain inputs (the post-GELU fc2 site carries only
        # gate_residual, which leaves x untouched).
        a_kind = self._kind_of(x)
        x = apply_norm_mod(x, norm_mod)
        self._reg(name, kind="linear", a_kind=a_kind,
                  x_shape=tuple(x.shape), w_shape=tuple(w.shape))
        y = x @ w
        if b is not None:
            y = y + b
        self.registry[name].out_shape = tuple(y.shape)
        return apply_gate_residual(y, gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        self._reg(name, kind="einsum", spec=spec, b_is_weight=b_is_weight,
                  a_kind=self._kind_of(a),
                  x_shape=tuple(a.shape), w_shape=tuple(b.shape))
        y = jnp.einsum(spec, a, b)
        self.registry[name].out_shape = tuple(y.shape)
        return y

    def act(self, name, x, kind):
        self._marks[id(x)] = (kind, x)
        self.acts[name] = kind
        return x

    def _kind_of(self, x) -> str:
        mark = self._marks.get(id(x))
        return mark[0] if mark is not None and mark[1] is x else "plain"


# ---------------------------------------------------------------------------
# calibration capture
# ---------------------------------------------------------------------------
def stable_seed(name: str, base: int = 0) -> int:
    """Deterministic per-op seed (hash() is salted per process)."""
    import zlib
    return base + (zlib.crc32(name.encode()) & 0xFFFF)


def _row_index(n_rows, max_rows, seed):
    """The rows a capture keeps of ``n_rows``: all (None), or a seeded
    draw of ``max_rows`` without replacement."""
    if n_rows <= max_rows:
        return None
    return np.random.default_rng(seed).choice(n_rows, max_rows,
                                              replace=False)


def _subsample_rows(x, max_rows, seed):
    """Flatten leading dims to rows and subsample; returns np.ndarray."""
    x = np.asarray(x)
    rows = x.reshape(-1, x.shape[-1])
    idx = _row_index(rows.shape[0], max_rows, seed)
    return rows if idx is None else rows[idx]


@dataclasses.dataclass
class CalibrationContext(OpContext):
    """Stores calibration tensors per op. Run EAGERLY (not under jit).

    store[name] = list of dicts per batch:
      linear: {'x': rows, 'g': fisher rows or None, 'tg': int}
      einsum: {'a': array, 'b': array (unless b_is_weight), 'g': ..., 'tg': int}
    Weights are captured once in ``weights[name]``.
    """
    registry: Dict[str, OpInfo] = dataclasses.field(default_factory=dict)
    store: Dict[str, List[dict]] = dataclasses.field(default_factory=dict)
    act_store: Dict[str, List[np.ndarray]] = dataclasses.field(
        default_factory=dict)
    hook_acts: frozenset = frozenset()    # act names quantized at the hook
    weights: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    max_rows_per_batch: int = 256
    max_batch_sub: int = 4        # batch-dim subsample for einsum operands
    _marks: Dict[int, str] = dataclasses.field(default_factory=dict)
    _seen: set = dataclasses.field(default_factory=set)
    seed: int = 0

    def begin_batch(self):
        """Reset per-forward dedup (only the FIRST call site of a shared
        op name is stored, matching the fisher tap alignment)."""
        self._seen.clear()

    def _tg(self):
        return int(self.tgroup) if self.tgroup is not None else 0

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        # Calibration captures the MODULATED tensor — the one the matmul
        # (and the fused kernel's quantize prologue) actually consumes.
        x = apply_norm_mod(x, norm_mod)
        if name not in self._seen:
            self._seen.add(name)
            if name not in self.weights:
                self.weights[name] = np.asarray(w)
            rows = _subsample_rows(x, self.max_rows_per_batch,
                                   stable_seed(name, self.seed))
            self.store.setdefault(name, []).append({"x": rows, "tg": self._tg()})
        y = x @ w
        if b is not None:
            y = y + b
        return apply_gate_residual(y, gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        if name not in self._seen:
            self._seen.add(name)
            sub = slice(0, self.max_batch_sub)
            rec = {"a": np.asarray(a[sub]), "tg": self._tg()}
            if b_is_weight:
                if name not in self.weights:
                    self.weights[name] = np.asarray(b)
            else:
                rec["b"] = np.asarray(b[sub])
            self.store.setdefault(name, []).append(rec)
        return jnp.einsum(spec, a, b)

    def act(self, name, x, kind):
        self._marks[id(x)] = kind
        if name in self.hook_acts and name not in self._seen:
            self._seen.add(name)
            self.act_store.setdefault(name, []).append(_subsample_rows(
                x, self.max_rows_per_batch, stable_seed(name, self.seed)))
        return x


# ---------------------------------------------------------------------------
# range capture: statistics reduced on the device
# ---------------------------------------------------------------------------
PROB_BINS_PER_OCTAVE = 8
PROB_OCTAVES = 24
PROB_BINS = PROB_BINS_PER_OCTAVE * PROB_OCTAVES
# rows of probabilities (one query's softmax each) a capture histograms per
# site and batch, strided evenly over batch entries, heads and queries: a
# scatter into the bins costs per element on a TPU, and 2,048 rows of 1,024
# keys describe the distribution as well as every row does
PROB_ROWS = 2048


def prob_bin_edges() -> np.ndarray:
    """The ``PROB_BINS + 1`` edges of the post-softmax histogram,
    log-spaced over [2^-24, 1]; bin 0 also takes everything below."""
    return 2.0 ** (np.arange(PROB_BINS + 1) / PROB_BINS_PER_OCTAVE
                   - PROB_OCTAVES)


@jax.jit
def prob_histogram(p):
    """``(PROB_BINS, 3)`` f32: per bin of ``prob_bin_edges``, the count of
    the probabilities ``p`` in it and their sum and sum of squares. Counts
    are exact up to 2^24 a bin."""
    p = p.astype(jnp.float32).reshape(-1)
    lg = jnp.log2(jnp.maximum(p, 2.0 ** -PROB_OCTAVES))
    idx = jnp.clip(jnp.floor((lg + PROB_OCTAVES) * PROB_BINS_PER_OCTAVE),
                   0, PROB_BINS - 1).astype(jnp.int32)
    rows = jnp.stack([jnp.ones_like(p), p, p * p], axis=-1)
    return jnp.zeros((PROB_BINS, 3), jnp.float32).at[idx].add(rows)


def key_moments(spec: str, b):
    """``[sum of mean^2, sum of variance]`` of einsum operand ``b`` over
    the axis it contracts with operand a (the keys of P V), summed over
    every other axis."""
    ins, out = spec.split("->")
    a_spec, b_spec = ins.split(",")
    axis = next(i for i, c in enumerate(b_spec)
                if c in a_spec and c not in out)
    b = b.astype(jnp.float32)
    m = jnp.mean(b, axis=axis, keepdims=True)
    return jnp.stack([jnp.sum(m * m),
                      jnp.sum(jnp.mean(jnp.square(b - m), axis=axis))])


@dataclasses.dataclass
class RangeCaptureContext(OpContext):
    """The range pipeline's capture. Run EAGERLY; every statistic is
    reduced ON THE DEVICE as the forward runs, and only ``reduce()``
    brings them to the host, so what the host holds does not grow with
    the number or size of calibration batches.

    stats[name][stat][tg] (device arrays until ``reduce``), folded over
    batches by max, or by sum for 'p_hist' and 'v_moments':
      linear 'x': ``[-min, max]`` of the row-subsampled input (the same
        rows ``CalibrationContext`` keeps);
      einsum 'a' / 'b': ``[absmax]`` of each operand; a post-softmax 'a'
        gets 'p_hist' instead (``prob_histogram`` of ``PROB_ROWS`` of
        its rows), and its 'b' (the values) also 'v_moments'
        (``key_moments``).
    Weights are captured once in ``weights[name]``, as in
    ``CalibrationContext``; they are the model's, not calibration data.
    """
    registry: Dict[str, OpInfo] = dataclasses.field(default_factory=dict)
    stats: Dict[str, Dict[str, Dict[int, Any]]] = dataclasses.field(
        default_factory=dict)
    weights: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    max_rows_per_batch: int = 256
    seed: int = 0
    _seen: set = dataclasses.field(default_factory=set)

    SUMMED = ("p_hist", "v_moments")

    def begin_batch(self):
        """Reset per-forward dedup (only the FIRST call site of a shared
        op name is captured, as in ``CalibrationContext``)."""
        self._seen.clear()

    def _first(self, name) -> bool:
        if name in self._seen:
            return False
        self._seen.add(name)
        return True

    def _add(self, name, stat, value):
        """Fold one batch's statistic into the running one."""
        per = self.stats.setdefault(name, {}).setdefault(stat, {})
        tg = int(self.tgroup) if self.tgroup is not None else 0
        prev = per.get(tg)
        if prev is not None:
            value = (prev + value if stat in self.SUMMED
                     else jnp.maximum(prev, value))
        per[tg] = value

    @staticmethod
    def _absmax(a):
        return jnp.max(jnp.abs(a)).astype(jnp.float32)[None]

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        x = apply_norm_mod(x, norm_mod)
        if self._first(name):
            if name not in self.weights:
                self.weights[name] = np.asarray(w)
            rows = x.reshape(-1, x.shape[-1])
            idx = _row_index(rows.shape[0], self.max_rows_per_batch,
                             stable_seed(name, self.seed))
            if idx is not None:
                rows = rows[jnp.asarray(idx)]
            self._add(name, "x", jnp.stack(
                [-jnp.min(rows), jnp.max(rows)]).astype(jnp.float32))
        y = x @ w
        if b is not None:
            y = y + b
        return apply_gate_residual(y, gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        if self._first(name):
            info = self.registry.get(name)
            if info is not None and info.a_kind == "post_softmax":
                rows = a.reshape(-1, a.shape[-1])
                stride = -(-rows.shape[0] // PROB_ROWS)
                self._add(name, "p_hist", prob_histogram(rows[::stride]))
                self._add(name, "v_moments", key_moments(spec, b))
            else:
                self._add(name, "a", self._absmax(a))
            if b_is_weight:
                if name not in self.weights:
                    self.weights[name] = np.asarray(b)
            else:
                self._add(name, "b", self._absmax(b))
        return jnp.einsum(spec, a, b)

    def act(self, name, x, kind):
        return x

    def reduce(self) -> Dict[str, Dict[str, Dict[int, Any]]]:
        """The statistics on the host (numpy), same nesting."""
        return jax.tree.map(np.asarray, jax.device_get(self.stats))


# ---------------------------------------------------------------------------
# fisher taps
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TapContext(OpContext):
    """Adds ``taps[name]`` to every op output; grad w.r.t. taps = dL/dz."""
    taps: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def _tap(self, name, y):
        t = self.taps.get(name)
        # shape guard: ops sharing a name across call sites with different
        # shapes (e.g. meta-token KV) only tap the recorded-shape site.
        if t is not None and tuple(t.shape) == tuple(y.shape):
            y = y + t
        return y

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        x = apply_norm_mod(x, norm_mod)
        y = x @ w
        if b is not None:
            y = y + b
        # tap the PRE-gate matmul output: dL/dz is defined on the op's
        # own output, exactly as when the model gated outside the seam.
        return apply_gate_residual(self._tap(name, y), gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        return self._tap(name, jnp.einsum(spec, a, b))

    def act(self, name, x, kind):
        return x


@dataclasses.dataclass
class ShapeContext(OpContext):
    """Records op OUTPUT shapes only (to build zero taps)."""
    shapes: Dict[str, tuple] = dataclasses.field(default_factory=dict)

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        x = apply_norm_mod(x, norm_mod)
        y = x @ w
        if b is not None:
            y = y + b
        self.shapes.setdefault(name, (tuple(y.shape), y.dtype))
        return apply_gate_residual(y, gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        y = jnp.einsum(spec, a, b)
        self.shapes.setdefault(name, (tuple(y.shape), y.dtype))
        return y

    def act(self, name, x, kind):
        return x


# ---------------------------------------------------------------------------
# quantized execution
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class QuantContext(OpContext):
    """Applies calibrated quantizers (fake-quant by default).

    qparams[name] = {
      'w': ChannelQ | None,            # weight / operand-b quantizer
      'x': UniformQ | MRQ* | TGQ | None,  # input / operand-a quantizer
      'x_prescale': array | None,      # PTQ4DiT-like channel balancing
      'out_bias': array | None,        # PTQD-like bias correction
    }
    kernel=True routes packed linears through the fused Pallas kernels
    ('int8' pack -> fused-quantize matmul at 8 or 6 bits, 'int8_mrq' pack
    -> single-pass MRQ matmul, 'int4' / 'int4_mrq' packs -> the
    nibble-packed int4 family with per-K-group weight scales) and whole
    attention blocks through the int8 attention kernels (the
    ``attention`` seam lowers when the op's '/qk' qparams carry an
    'int8_qk' pack and its '/pv' qparams an 'int8_pv' pack; the packs'
    ``bits`` tag sets the code range, and 4-bit flash streams
    nibble-packed kv); the TGQ timestep group (``self.tgroup``, possibly
    traced) is resolved inside the kernels — no per-group repacking or
    retracing.

    ``attn_impl`` picks the attention lowering (kernel=True only):
    'flash' (default) runs the whole block as ONE Pallas kernel —
    ``kernels.flash_attn_mrq``: int8 QK^T -> online softmax -> MRQ codes
    -> dual-region P·V with the (S, S) scores/codes never touching HBM;
    'composed' keeps the three-kernel chain (``int8_bmm_qk`` ->
    ``softmax_mrq_codes`` -> ``int8_bmm_pv``) — the exactness oracle the
    flash path is toleranced against (``ref.flash_vs_composed_atol``).
    """
    qparams: Dict[str, dict] = dataclasses.field(default_factory=dict)
    kernel: bool = False
    attn_impl: str = "flash"

    def split_arrays(self):
        """The qparams' array leaves (quantizer parameters and kernel
        packs); every other leaf — bit widths, group counts — stays static
        in the rebuilt context. See ``OpContext.split_arrays``."""
        leaves, tree = jax.tree.flatten(self.qparams)
        idx = [i for i, a in enumerate(leaves)
               if isinstance(a, (jax.Array, np.ndarray))]

        def rebuild(arrays):
            out = list(leaves)
            for i, a in zip(idx, arrays):
                out[i] = a
            return dataclasses.replace(
                self, qparams=jax.tree.unflatten(tree, out))
        return tuple(leaves[i] for i in idx), rebuild

    def _q_in(self, qp, x):
        q = qp.get("x")
        pre = qp.get("x_prescale")
        if pre is not None:
            x = x / pre
        x = apply_quantizer(q, x, tgroup=self.tgroup)
        return x

    def _q_w(self, qp, w):
        pre = qp.get("x_prescale")
        if pre is not None:
            # fold the balancing factor into the weight's input dim
            w = w * pre.reshape((-1,) + (1,) * (w.ndim - 1)) if w.ndim >= 1 else w
        return apply_quantizer(qp.get("w"), w, tgroup=self.tgroup)

    @staticmethod
    def _fold_out_bias(b, ob, gate_residual):
        """When the gate+residual epilogue is fused, the PTQD bias
        correction must land INSIDE the gate — fold it into the matmul
        bias (``residual + gate * (y + ob)``). Unfused, it stays a
        post-add. Returns (bias, post_add)."""
        if ob is None or gate_residual is None:
            return b, ob
        return (ob if b is None else b + ob), None

    def linear(self, name, x, w, b=None, norm_mod=None, gate_residual=None):
        qp = self.qparams.get(name)
        if qp is None:
            x = apply_norm_mod(x, norm_mod)
            y = x @ w
            y = y + b if b is not None else y
            return apply_gate_residual(y, gate_residual)
        if self.kernel:
            # All four pack families fuse the adaLN chains: norm_mod
            # runs in the kernels' quantize prologue, gate_residual in
            # the dequant epilogue (single HBM write).
            for key, fn in (("int8", "int8_linear"),
                            ("int8_mrq", "int8_linear_mrq"),
                            ("int4", "int4_linear"),
                            ("int4_mrq", "int4_linear_mrq")):
                if qp.get(key) is not None:
                    from repro.kernels import ops as kops
                    bias, ob = self._fold_out_bias(b, qp.get("out_bias"),
                                                   gate_residual)
                    y = getattr(kops, fn)(
                        x, qp[key], bias=bias, tgroup=self.tgroup,
                        norm_mod=norm_mod, gate_residual=gate_residual)
                    return y + ob if ob is not None else y
        x = apply_norm_mod(x, norm_mod)
        x = self._q_in(qp, x)
        w = self._q_w(qp, w)
        y = x @ w
        if b is not None:
            y = y + b
        ob = qp.get("out_bias")
        y = y + ob if ob is not None else y
        return apply_gate_residual(y, gate_residual)

    def einsum(self, name, spec, a, b, b_is_weight=False):
        qp = self.qparams.get(name)
        if qp is None:
            return jnp.einsum(spec, a, b)
        a = self._q_in(qp, a)
        bq = qp.get("w") if b_is_weight else qp.get("b")
        b = apply_quantizer(bq, b, tgroup=self.tgroup)
        y = jnp.einsum(spec, a, b)
        ob = qp.get("out_bias")
        return y + ob if ob is not None else y

    def attention(self, name, q, k, v, *, mask=None, scale=1.0):
        # The attention seam lowers to the int8 Pallas kernels exactly
        # like ctx.linear sites: when serving packs exist for BOTH
        # matmuls, the whole block runs int8 with the probs never in HBM
        # as fp — as ONE flash kernel (attn_impl='flash', scores/codes
        # never in HBM at all) or the composed three-kernel chain
        # (attn_impl='composed'). Otherwise fall back to the composed
        # fake-quant seams (OpContext default).
        if self.kernel:
            qk_qp = self.qparams.get(f"{name}/qk") or {}
            pv_qp = self.qparams.get(f"{name}/pv") or {}
            if (qk_qp.get("int8_qk") is not None
                    and pv_qp.get("int8_pv") is not None):
                from repro.kernels import ops as kops
                if self.attn_impl == "flash":
                    return kops.flash_attention(
                        q, k, v, qk_qp["int8_qk"], pv_qp["int8_pv"],
                        mask=mask, scale=scale, tgroup=self.tgroup)
                if self.attn_impl != "composed":
                    raise ValueError(
                        f"QuantContext.attn_impl must be 'flash' or "
                        f"'composed', got {self.attn_impl!r}")
                return kops.int8_attention(
                    q, k, v, qk_qp["int8_qk"], pv_qp["int8_pv"], mask=mask,
                    scale=scale, tgroup=self.tgroup)
        return OpContext.attention(self, name, q, k, v, mask=mask,
                                   scale=scale)

    def act(self, name, x, kind):
        # post-softmax / post-GELU quantize at the consuming matmul (where
        # the HO objective is defined); hook-quantized acts (SwiGLU silu
        # gates, which feed an elementwise product first) quantize here.
        qp = self.qparams.get(name)
        if qp is not None and "act" in qp:
            return apply_quantizer(qp["act"], x, tgroup=self.tgroup)
        return x
