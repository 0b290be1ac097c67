"""Sharding rules: logical-axis mapping, divisibility guard, spec trees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import (
    batch_axes, bind_logical, logical_axes, param_specs,
)
from repro.launch.mesh import make_debug_mesh


@pytest.fixture(scope="module")
def mesh11():
    # 1x1 mesh works on one CPU device but exercises the rule machinery
    return make_debug_mesh(1, 1)


def test_logical_axes_rules():
    assert logical_axes("blocks/attn/q/w", 3) == (None, "fsdp", "tp")
    assert logical_axes("blocks/mlp/down/w", 3) == (None, "tp", "fsdp")
    assert logical_axes("blocks/mlp/gate", 4) == (None, "ep", "fsdp", None)
    # embedding/head tables shard vocab ONLY: FSDP on d_model (the logits
    # contraction dim) makes GSPMD partial-sum full-batch logits
    # (EXPERIMENTS §Perf, qwen2.5-14b: 37 GiB/device all-reduce)
    assert logical_axes("embed/emb", 2) == ("vocab", None)
    assert logical_axes("head/w", 2) == (None, "vocab")
    assert logical_axes("blocks/mlp/router/w", 3) == (None, None, None)
    assert logical_axes("unknown/thing", 2) == (None, None)
    # shared-expert dense rules win over the raw-expert rule
    assert logical_axes("blocks/mlp/shared/gate/w", 3) == (None, "fsdp", "tp")


def test_divisibility_guard(mesh11):
    mesh16 = _fake_mesh16()
    # vocab 51865 (whisper) is not divisible by 16 -> replicated
    spec = bind_logical(("vocab", None), (51865, 384), mesh16, fsdp=False)
    assert spec == P(None, None)
    spec = bind_logical(("vocab", None), (151936, 2048), mesh16, fsdp=False)
    assert spec == P("model", None)


def _fake_mesh16():
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)
    return FakeMesh()


def test_fsdp_binding():
    mesh16 = _fake_mesh16()
    on = bind_logical(("fsdp", "tp"), (2048, 11008), mesh16, fsdp=True)
    off = bind_logical(("fsdp", "tp"), (2048, 11008), mesh16, fsdp=False)
    assert on == P("data", "model")
    assert off == P(None, "model")


def test_param_specs_tree_matches(mesh11):
    from repro.configs import get_smoke
    from repro.models import lm_init
    cfg = get_smoke("qwen3-1.7b")
    p = lm_init(jax.random.PRNGKey(0), cfg)
    specs = param_specs(p, mesh11, fsdp=False)
    flat_p = jax.tree.leaves(p)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)


def test_moe_expert_specs(mesh11):
    mesh16 = _fake_mesh16()
    # deepseek expert stack (160, 5120, 1536): EP on experts + FSDP on d
    spec = bind_logical(logical_axes("blocks/mlp/gate", 4),
                        (60, 160, 5120, 1536), mesh16, fsdp=True)
    assert spec == P(None, "model", "data", None)


def test_batch_axes(mesh11):
    assert batch_axes(mesh11) == ("data",)

    class FakeMulti:
        axis_names = ("pod", "data", "model")
    assert batch_axes(FakeMulti()) == ("pod", "data")


def test_jit_with_specs_runs(mesh11):
    """End-to-end: sharded jit of a smoke train step on the 1x1 mesh."""
    from repro.configs import get_smoke
    from repro.models import lm_init, lm_loss_fn
    from jax.sharding import NamedSharding
    cfg = get_smoke("qwen2.5-3b")
    p = lm_init(jax.random.PRNGKey(0), cfg)
    shard = jax.tree.map(lambda s: NamedSharding(mesh11, s),
                         param_specs(p, mesh11))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    with mesh11:
        f = jax.jit(lambda pp, bb: lm_loss_fn(pp, cfg, bb)[0],
                    in_shardings=(shard, NamedSharding(mesh11, P())))
        assert np.isfinite(float(f(p, batch)))
