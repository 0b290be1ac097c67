"""`QuantArtifact` — calibrated quantization state as a first-class,
serializable value.

The artifact is everything a serving process needs to cold-start a
quantized deployment WITHOUT rerunning calibration: the per-op ``qparams``
(quantizer pytrees plus the packed kernel parameters — int8/int6 byte
codes or nibble-packed int4 weight payloads), the :class:`QuantRecipe`
that produced
them, and provenance metadata (model/diffusion configs, TGQ group
boundaries, calibration stats, caller-supplied git sha / timestamp).

On-disk layout (``artifact.save(path)``)::

    <path>/artifact.json        # version, recipe, meta, structure spec
    <path>/step_00000000/       # array leaves via checkpoint/ckpt.py
        manifest.json           #   (atomic npz shards, _COMMITTED marker)
        shard_00000.npz
    <path>/latest

Array leaves ride the repo's fault-tolerant checkpoint machinery
(`repro.checkpoint.ckpt`); the *structure* — which quantizer class wraps
which arrays, pack dict keys, meta fields like ``bits`` — is encoded to a
JSON spec by this module, so ``QuantArtifact.load`` reconstructs the
exact pytree in a fresh process with no pickle and no reliance on jax
treedef protos. Round-trips are bit-exact (dtypes preserved through the
npz shards), which is what makes loaded-artifact serving sample-identical
to in-memory serving (asserted in ``tests/test_quant_api.py``).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.core.quantizers import (
    ChannelQ, MRQSignedQ, MRQSoftmaxQ, SymQ, TGQ, UniformQ,
)
from repro.quant.recipe import QuantRecipe

ARTIFACT_VERSION = 1
_ARTIFACT_JSON = "artifact.json"

# the quantizer containers an artifact may carry; encoded by class name +
# per-field spec so load() never needs pickle
_QUANTIZERS = {c.__name__: c for c in
               (UniformQ, SymQ, ChannelQ, MRQSoftmaxQ, MRQSignedQ, TGQ)}


# ---------------------------------------------------------------------------
# structure spec: tree -> (json spec, flat array leaves)
# ---------------------------------------------------------------------------
def _encode(obj: Any, leaves: List[np.ndarray]) -> dict:
    if obj is None:
        return {"k": "none"}
    if isinstance(obj, bool) or isinstance(obj, (int, float, str)) and \
            not isinstance(obj, np.generic):
        return {"k": "py", "v": obj}
    if isinstance(obj, dict):
        if not all(isinstance(k, str) for k in obj):
            raise TypeError("artifact dicts must be str-keyed")
        return {"k": "dict", "items": {k: _encode(v, leaves)
                                       for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"k": "tuple" if isinstance(obj, tuple) else "list",
                "items": [_encode(v, leaves) for v in obj]}
    if type(obj).__name__ in _QUANTIZERS and dataclasses.is_dataclass(obj):
        return {"k": "q", "cls": type(obj).__name__,
                "fields": {f.name: _encode(getattr(obj, f.name), leaves)
                           for f in dataclasses.fields(obj)}}
    if isinstance(obj, (np.ndarray, np.generic, jax.Array)):
        leaves.append(np.asarray(obj))
        return {"k": "arr", "i": len(leaves) - 1}
    raise TypeError(f"cannot serialize {type(obj).__name__} into a "
                    "QuantArtifact (supported: dict/list/tuple, scalars, "
                    f"arrays, {sorted(_QUANTIZERS)})")


def _fmt(v: Any) -> str:
    """A calibration counter for ``summary()``: numbers to 3 figures,
    lists compactly."""
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_fmt(x) for x in v) + "]"
    return f"{v:.3g}" if isinstance(v, float) else str(v)


def _decode(spec: dict, leaves: List[Any]) -> Any:
    k = spec["k"]
    if k == "none":
        return None
    if k == "py":
        return spec["v"]
    if k == "dict":
        return {key: _decode(s, leaves) for key, s in spec["items"].items()}
    if k in ("list", "tuple"):
        seq = [_decode(s, leaves) for s in spec["items"]]
        return tuple(seq) if k == "tuple" else seq
    if k == "q":
        cls = _QUANTIZERS[spec["cls"]]
        return cls(**{n: _decode(s, leaves)
                      for n, s in spec["fields"].items()})
    if k == "arr":
        return leaves[spec["i"]]
    raise ValueError(f"unknown artifact spec node kind {k!r}")


# ---------------------------------------------------------------------------
# the artifact
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class QuantArtifact:
    """qparams + recipe + provenance. See the module docstring.

    ``meta`` keys written by :func:`repro.quant.quantize`:
      model        {"class": "DiTCfg", "cfg": {...}}     (reconstructable)
      dif          {...DiffusionCfg fields...}
      tgq_groups   effective G; tgq_group_boundaries: [[lo, hi), ...]
      calib        pipeline stats (n_quantized, wall_s, ... — no tensors)
      provenance   caller-supplied (git sha, timestamp, arch label, ...)
    """
    qparams: Dict[str, dict]
    recipe: QuantRecipe
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- consumption --------------------------------------------------------
    @property
    def has_kernel_packs(self) -> bool:
        return any(any(p in qp for p in ("int8", "int8_mrq", "int4",
                                         "int4_mrq", "int8_qk", "int8_pv"))
                   for qp in self.qparams.values())

    def fallback_ops(self) -> List[str]:
        """Op names that would take the fake-quant path under
        ``context(kernel=True)`` — quantized matmul ops whose qparams
        carry NO kernel pack. Empty list == every quantized matmul in
        the artifact lowers onto a Pallas kernel (the zero-fallback
        deployment contract; ``launch.serve`` names these ops in its
        fallback warning). Activation-only entries (softmax/GELU hooks)
        are not matmuls and are never counted."""
        out: List[str] = []
        for name in sorted(self.qparams):
            qp = self.qparams[name]
            if name.endswith("/qk"):
                if "int8_qk" not in qp:
                    out.append(name)
            elif name.endswith("/pv"):
                if "int8_pv" not in qp:
                    out.append(name)
            elif "w" in qp and not any(
                    p in qp for p in ("int8", "int8_mrq", "int4",
                                      "int4_mrq")):
                out.append(name)
        return out

    def context(self, kernel: Optional[bool] = None,
                attn_impl: Optional[str] = None):
        """The op context serving this artifact — replaces
        ``make_quant_context``. ``kernel=None`` auto-selects the fused
        int8 kernel path exactly when the artifact carries packs;
        ``attn_impl=None`` uses the recipe's recorded attention lowering
        ('flash' fused single-kernel / 'composed' three-kernel oracle —
        both consume the same packs, so overriding is always safe)."""
        from repro.core.contexts import QuantContext
        if kernel is None:
            kernel = self.has_kernel_packs
        if kernel and not self.has_kernel_packs:
            raise ValueError(
                "artifact has no kernel packs (recipe "
                f"{self.recipe.bits}/{self.recipe.method}); serve it with "
                "kernel=False (fake-quant) or re-quantize with a "
                "kernel-deployable recipe")
        if attn_impl is None:
            attn_impl = self.recipe.attn_impl
        return QuantContext(qparams=self.qparams, kernel=kernel,
                            attn_impl=attn_impl)

    # -- model identity -----------------------------------------------------
    @property
    def params_hash(self) -> Optional[dict]:
        """The fp-params content hash recorded at quantize() time
        (``checkpoint.ckpt.content_hash``), or None for artifacts written
        before hashes were recorded."""
        return self.meta.get("params_hash")

    def check_params(self, params) -> None:
        """Fail fast if ``params`` is not the fp tree this artifact was
        calibrated against. Artifacts without a recorded hash (older
        format) pass — there is nothing to check against."""
        want = self.params_hash
        if want is None:
            return
        got = ckpt.content_hash(params)
        if got["digest"] == want["digest"]:
            return
        if got["n_leaves"] != want["n_leaves"]:
            raise ValueError(
                f"params mismatch: artifact was calibrated against a tree "
                f"with {want['n_leaves']} leaves, got {got['n_leaves']} — "
                "wrong checkpoint for this artifact?")
        n_bad = sum(1 for a, b in zip(got["leaves"], want["leaves"])
                    if a != b)
        raise ValueError(
            f"params content hash mismatch: {n_bad}/{want['n_leaves']} "
            f"leaves differ from the fp params this artifact was "
            f"calibrated against (digest {got['digest']} != "
            f"{want['digest']}) — wrong checkpoint for this artifact?")

    def model_cfg(self):
        m = self.meta.get("model") or {}
        if m.get("class") != "DiTCfg":
            raise ValueError(f"artifact has no DiTCfg metadata (model = "
                             f"{m.get('class')!r})")
        from repro.models.dit import DiTCfg
        return DiTCfg(**m["cfg"])

    def dif_cfg(self):
        if "dif" not in self.meta:
            raise ValueError("artifact has no DiffusionCfg metadata")
        from repro.diffusion import DiffusionCfg
        return DiffusionCfg(**self.meta["dif"])

    def summary(self) -> str:
        n8 = sum(1 for qp in self.qparams.values()
                 if "int8" in qp or "int8_mrq" in qp)
        n4 = sum(1 for qp in self.qparams.values()
                 if "int4" in qp or "int4_mrq" in qp)
        n_attn = sum(1 for qp in self.qparams.values() if "int8_qk" in qp)
        packs = f"{n8} int8 linear packs"
        if n4:
            packs = f"{n4} packed-int4 linear packs"
        cal = self.meta.get("calib", {})
        counters = "".join(
            f", {k}={_fmt(cal[k])}" for k in
            ("capture_host_bytes", "softmax_s1", "softmax_r2_share")
            if k in cal)
        return (f"QuantArtifact({self.recipe.bits}/{self.recipe.method}: "
                f"{len(self.qparams)} ops, {packs}, "
                f"{n_attn} int8 attention blocks, "
                f"G={self.meta.get('tgq_groups')}{counters})")

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> str:
        """Save under ``path`` (a directory). Returns ``path``.

        Leaf shards commit first (atomically, via ckpt's ``_COMMITTED``
        rename), then ``artifact.json`` replaces atomically. The json
        records the shard checksums from the ckpt manifest, so a crash
        BETWEEN the two steps when overwriting an existing artifact
        (old json + new shards) is detected at load time instead of
        silently decoding new leaves under a stale spec/recipe.
        """
        leaves: List[np.ndarray] = []
        spec = _encode(self.qparams, leaves)
        os.makedirs(path, exist_ok=True)
        step_dir = ckpt.save(path, step=0, tree=leaves, keep=1)
        with open(os.path.join(step_dir, "manifest.json")) as f:
            leaf_hashes = json.load(f)["hashes"]
        doc = {
            "version": ARTIFACT_VERSION,
            "recipe": self.recipe.to_dict(),
            "meta": self.meta,
            "n_leaves": len(leaves),
            "leaf_hashes": leaf_hashes,
            "spec": spec,
        }
        tmp = os.path.join(path, _ARTIFACT_JSON + ".tmp")
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, os.path.join(path, _ARTIFACT_JSON))
        return path

    @classmethod
    def load(cls, path: str, expect_recipe: Optional[QuantRecipe] = None,
             params=None) -> "QuantArtifact":
        """Load from ``path``. With ``expect_recipe``, raise ``ValueError``
        if the stored recipe differs (field-by-field diff in the message)
        — the cold-start guard against serving a stale/mismatched
        deployment artifact. With ``params``, additionally verify the fp
        tree against the artifact's recorded content hash
        (:meth:`check_params`) — the wrong-checkpoint guard
        (``ServeEngine.from_artifact`` runs the same check)."""
        doc_path = os.path.join(path, _ARTIFACT_JSON)
        if not os.path.exists(doc_path):
            raise FileNotFoundError(f"no quantization artifact at {path} "
                                    f"(missing {_ARTIFACT_JSON})")
        with open(doc_path) as f:
            doc = json.load(f)
        if doc.get("version") != ARTIFACT_VERSION:
            raise ValueError(f"artifact version {doc.get('version')} != "
                             f"supported {ARTIFACT_VERSION}")
        recipe = QuantRecipe.from_dict(doc["recipe"])
        if expect_recipe is not None and expect_recipe != recipe:
            raise ValueError(
                "artifact recipe mismatch: "
                + "; ".join(f"{k}: artifact={a!r} expected={b!r}"
                            for k, (a, b) in recipe.diff(expect_recipe)
                            .items()))

        step = ckpt.latest_step(path)
        if step is None:
            raise FileNotFoundError(f"artifact at {path} has no committed "
                                    "leaf checkpoint")
        with open(os.path.join(path, f"step_{step:08d}",
                               "manifest.json")) as f:
            manifest = json.load(f)
        if manifest["hashes"] != doc["leaf_hashes"]:
            raise ValueError(
                f"artifact at {path} is inconsistent: artifact.json does "
                "not match the committed leaf checkpoint (interrupted "
                "overwrite?) — re-save the artifact")
        like = [jax.ShapeDtypeStruct(tuple(s), np.dtype(d))
                for s, d in zip(manifest["shapes"], manifest["dtypes"])]
        if len(like) != doc["n_leaves"]:
            raise ValueError(f"leaf count drift at {path}: spec "
                             f"{doc['n_leaves']} vs ckpt {len(like)}")
        # fail-fast on bit-rot BEFORE np.load touches the shards: a corrupt
        # byte otherwise surfaces as a cryptic zip/zlib exception (or
        # silently wrong leaves) far from the artifact path
        ckpt.verify_shards(path, step=step)
        leaves = ckpt.restore(path, like, step=step) if like else []
        qparams = _decode(doc["spec"], list(leaves))
        art = cls(qparams=qparams, recipe=recipe, meta=doc["meta"])
        if params is not None:
            art.check_params(params)
        return art
