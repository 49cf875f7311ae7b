"""Data pipeline determinism/resume + sharding-rule properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import sharding as shd
from repro.models import transformer


def test_pipeline_deterministic_and_resumable():
    cfg = registry.get_smoke_config("llama3.2-1b")
    d = DataConfig(batch=4, seq_len=16, seed=7)
    p1 = SyntheticPipeline(cfg, d)
    batches = [p1.next_batch() for _ in range(5)]
    st_ = p1.state_dict()

    # resume from step 3 reproduces batches 3, 4
    p2 = SyntheticPipeline(cfg, d)
    p2.load_state_dict({"step": 3, "seed": 7})
    for i in (3, 4):
        b = p2.next_batch()
        np.testing.assert_array_equal(np.asarray(b["tokens"]), np.asarray(batches[i]["tokens"]))
    assert st_["step"] == 5


def test_pipeline_labels_are_shifted_tokens():
    cfg = registry.get_smoke_config("llama3.2-1b")
    p = SyntheticPipeline(cfg, DataConfig(batch=2, seq_len=12, seed=0))
    b = p.next_batch()
    np.testing.assert_array_equal(
        np.asarray(b["tokens"][:, 1:]), np.asarray(b["labels"][:, :-1])
    )


def _mesh(dp, tp):
    n = dp * tp
    if n > 1:
        pytest.skip("single-device test process")
    return jax.make_mesh((dp, tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.mark.parametrize("arch", registry.list_archs())
def test_param_specs_cover_every_leaf(arch):
    """Every param leaf gets a spec whose sharded dims divide evenly on the
    production mesh extents (16, 16) — checked abstractly, no devices."""
    cfg = registry.get_config(arch)
    params_sds = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = shd.ShardingRules(
        mesh=FakeMesh(), dp_axes=("data",), fsdp_axis="data", tp_axis="model",
        attn_heads_sharded=cfg.n_heads > 0 and cfg.n_heads % 16 == 0,
        kv_heads_sharded=cfg.n_kv_heads > 0 and cfg.n_kv_heads % 16 == 0,
        ep=cfg.n_experts > 0 and cfg.n_experts % 16 == 0,
    )
    specs = shd.param_specs(cfg, rules, params_sds)
    sizes = {"data": 16, "model": 16}
    for (path, leaf), (_, spec) in zip(
        jax.tree_util.tree_flatten_with_path(params_sds)[0],
        jax.tree_util.tree_flatten_with_path(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))[0],
    ):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            total = 1
            for a in axes:
                total *= sizes[a]
            assert dim % total == 0, f"{path}: dim {dim} not divisible by {axes}"


@given(
    h=st.sampled_from([8, 16, 32, 40, 48, 64]),
    kv=st.sampled_from([1, 2, 4, 8, 16, 32]),
)
@settings(max_examples=30, deadline=None)
def test_kv_repeat_factor_properties(h, kv):
    """Outside a context the factor is 1; algebraic properties hold."""
    if h % kv:
        return
    assert shd.kv_repeat_factor(h, kv) == 1  # no active context


def test_manual_region_disables_dp_constraints():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    r = shd.ShardingRules(
        mesh=FakeMesh(), dp_axes=("data",), fsdp_axis="data", tp_axis="model",
        attn_heads_sharded=True, kv_heads_sharded=True, ep=False,
    )
    inner = r.manual_region()
    assert inner.batch_axes(8) is None
    assert inner.fsdp_axis is None
    assert inner.tp_axis == "model"  # TP constraints still active
