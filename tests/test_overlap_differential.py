"""Differential suite for the ready-bucket grad-sync overlap (DESIGN.md S16).

The overlap path MUST be a pure reordering: the same BucketLayout, the same
per-bucket stage math, only the *issue order* changes.  Three layers of
bit-exactness checks:

1. engine:   BucketPipeline admit/advance/drain == CollectivePlan.run_buffers
             for every schedule family, p in {2,3,5,8}, staggered admission;
2. gradient: segmented (3-VJP) backward == the monolithic value_and_grad
             backward, across model families and microbatch counts;
3. end-to-end (slow, 8 host devices): a full jitted train step with
             ``overlap=True`` == ``overlap=False`` — per-step losses and the
             entire final state tree bitwise, for all four converted
             grad-sync modes, non-power-of-two DP extents, a bf16 param
             variant, and the compressed mode's EF residual carry.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.collectives import plans  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticPipeline  # noqa: E402
from repro.distributed.gradsync import common, overlap as overlap_lib  # noqa: E402


# ---------------------------------------------------------------------------
# 1. BucketPipeline == run_buffers (sim executor, bitwise)
# ---------------------------------------------------------------------------

def _sim_bufs(plan, p, n_buckets=4, seed=0):
    q = plan.pad_quantum()
    rng = np.random.default_rng(seed)
    bufs = []
    for i in range(n_buckets):
        n = q * (i + 2)
        bufs.append(jnp.asarray(
            rng.standard_normal((p, n)).astype(np.float32)))
    return bufs


def _pipeline_staggered(plan, bufs):
    """Admit bucket k only after k advance() rounds — the worst-case
    interleaving the overlap path can produce (every bucket at a different
    stage depth while later ones are still being admitted)."""
    pipe = plan.pipeline()
    out = {}
    for k, b in enumerate(bufs):
        pipe.admit(k, b)
        pipe.advance()
    out.update(pipe.drain())
    return [out[k] for k in range(len(bufs))]


_PLAN_FAMILIES = {
    "mrd_ar": lambda p: plans.allreduce_plan(
        schedule="mrd", p=p, op="sum", executor="sim"),
    "rabenseifner_ar": lambda p: plans.allreduce_plan(
        schedule="rabenseifner", p=p, op="sum", executor="sim"),
    "mrd_ar_int8": lambda p: plans.allreduce_plan(
        schedule="mrd", p=p, op="sum", transform="int8", executor="sim"),
    "primitive_rs": lambda p: plans.reduce_scatter_plan(
        p=p, op="sum", executor="sim"),
}


@pytest.mark.parametrize("family", sorted(_PLAN_FAMILIES))
@pytest.mark.parametrize("p", [2, 3, 5, 8])
def test_pipeline_matches_run_buffers(family, p):
    plan = _PLAN_FAMILIES[family](p)
    bufs = _sim_bufs(plan, p, seed=p)
    want = plan.run_buffers([b for b in bufs])
    got = _pipeline_staggered(plan, bufs)
    assert len(got) == len(want)
    for k, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (
            f"{family} p={p} bucket {k}: staggered pipeline diverges from "
            f"run_buffers")


def test_pipeline_all_admitted_up_front_matches():
    """Admitting everything before the first advance() (the no-overlap
    admission order driven through the same engine) is also bitwise equal."""
    plan = _PLAN_FAMILIES["mrd_ar"](5)
    bufs = _sim_bufs(plan, 5, seed=42)
    want = plan.run_buffers([b for b in bufs])
    pipe = plan.pipeline()
    for k, b in enumerate(bufs):
        pipe.admit(k, b)
    out = pipe.drain()
    for k, w in enumerate(want):
        assert np.array_equal(np.asarray(out[k]), np.asarray(w))


def test_pipeline_duplicate_admit_rejected():
    plan = _PLAN_FAMILIES["mrd_ar"](3)
    bufs = _sim_bufs(plan, 3, n_buckets=2)
    pipe = plan.pipeline()
    pipe.admit(0, bufs[0])
    with pytest.raises(ValueError):
        pipe.admit(0, bufs[1])


# ---------------------------------------------------------------------------
# 2. segmented_grads == microbatched_grads (single device, bitwise)
# ---------------------------------------------------------------------------

def _collect_segmented(params, batch, cfg, mb):
    gen = overlap_lib.segmented_grads(params, batch, cfg, None, mb)
    loss, metrics = next(gen)
    merged = {}
    names = []
    for name, piece in gen:
        names.append(name)
        merged.update(piece)
    assert names == list(overlap_lib.GROUP_NAMES)
    grads = {k: merged[k] for k in params}
    return loss, metrics, grads


_SEG_ARCHS = ["llama3.2-1b", "gemma3-12b", "mixtral-8x7b", "falcon-mamba-7b"]


@pytest.mark.parametrize("arch", _SEG_ARCHS)
def test_segmented_grads_bitwise(arch):
    cfg = registry.get_smoke_config(arch)
    params = jax.jit(lambda k: __import__(
        "repro.models.transformer", fromlist=["transformer"]
    ).init_params(cfg, k))(jax.random.PRNGKey(0))
    batch = SyntheticPipeline(cfg, DataConfig(batch=4, seq_len=16, seed=0)).next_batch()

    ref_grads, ref_loss, _ = jax.jit(
        lambda p, b: common.microbatched_grads(p, b, cfg, None, 1)
    )(params, batch)
    loss, _, grads = jax.jit(
        lambda p, b: _collect_segmented(p, b, cfg, 1)
    )(params, batch)

    assert np.asarray(loss) == np.asarray(ref_loss)
    mism = []
    jax.tree_util.tree_map_with_path(
        lambda path, a, b: mism.append(jax.tree_util.keystr(path))
        if not np.array_equal(np.asarray(a), np.asarray(b)) else None,
        grads, ref_grads,
    )
    assert not mism, f"{arch}: segmented grads differ bitwise at {mism[:5]}"


def test_segmented_grads_bitwise_microbatched():
    """mb=2: the first microbatch runs through the identical segmented
    path under scan — grads must stay bitwise (the scalar mean loss may
    re-associate inside XLA fusion, so it only gets allclose)."""
    cfg = registry.get_smoke_config("llama3.2-1b")
    from repro.models import transformer

    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = SyntheticPipeline(cfg, DataConfig(batch=4, seq_len=16, seed=0)).next_batch()

    ref_grads, ref_loss, _ = jax.jit(
        lambda p, b: common.microbatched_grads(p, b, cfg, None, 2)
    )(params, batch)
    loss, _, grads = jax.jit(
        lambda p, b: _collect_segmented(p, b, cfg, 2)
    )(params, batch)

    np.testing.assert_allclose(
        np.asarray(loss), np.asarray(ref_loss), rtol=1e-6)
    ok = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        grads, ref_grads)
    assert all(jax.tree.leaves(ok)), "mb=2 segmented grads differ bitwise"


def test_group_partition_covers_params():
    """Every top-level param key lands in exactly one readiness group and
    the per-leaf group labels agree with the key offsets."""
    cfg = registry.get_smoke_config("gemma3-12b")
    from repro.models import transformer

    pshape = jax.eval_shape(
        lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0))
    head, stack, embed = overlap_lib._split_params(pshape)
    assert set(head) | set(stack) | set(embed) == set(pshape.keys())
    assert not (set(head) & set(stack)) and not (set(stack) & set(embed))
    lgroups = overlap_lib.leaf_groups(pshape)
    offs = overlap_lib.key_offsets(pshape)
    for k in pshape:
        g = overlap_lib.group_of_key(k)
        n = len(jax.tree.leaves(pshape[k]))
        assert lgroups[offs[k]: offs[k] + n] == [g] * n


# ---------------------------------------------------------------------------
# 3. End-to-end: jitted train step, overlap on == off (8 devices, slow)
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent(
    """
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import AxisType
    from repro.configs import registry
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.distributed import step as step_lib
    from repro.optim.optimizer import OptimizerConfig

    def run(mode, dp, overlap, cfg, steps=3):
        mesh = jax.make_mesh(
            (dp,), ("data",),
            axis_types=(AxisType.Auto,),
            devices=jax.devices()[:dp],
        )
        tcfg = step_lib.TrainConfig(
            microbatches=1, remat="none", grad_sync=mode,
            monitor=False, bucket_bytes=1 << 15, overlap=overlap,
            optimizer=OptimizerConfig(lr=1e-3, schedule="const", warmup_steps=0),
        )
        train_step, init_state, state_specs, rules = step_lib.make_train_step(
            cfg, mesh, tcfg)
        with mesh:
            state = init_state(jax.random.PRNGKey(0))
            from jax.sharding import NamedSharding
            shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), state_specs(state))
            state = jax.device_put(state, shardings)
            pipe = SyntheticPipeline(
                cfg, DataConfig(batch=8, seq_len=16, seed=1), mesh)
            jstep = jax.jit(train_step)
            losses = []
            for _ in range(steps):
                state, metrics = jstep(state, pipe.next_batch())
                losses.append(np.asarray(metrics["loss"]))
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
            flat[jax.tree_util.keystr(path)] = np.asarray(jax.device_get(leaf))
        return losses, flat

    def compare(mode, dp, cfg, tag=""):
        l0, s0 = run(mode, dp, False, cfg)
        l1, s1 = run(mode, dp, True, cfg)
        for i, (a, b) in enumerate(zip(l0, l1)):
            assert np.array_equal(a, b), (
                f"{mode}{tag} dp={dp} step {i}: loss {a!r} != {b!r}")
        assert set(s0) == set(s1)
        for k in s0:
            assert np.array_equal(s0[k], s1[k]), (
                f"{mode}{tag} dp={dp}: state leaf {k} differs bitwise")
        print(f"OK {mode}{tag} dp={dp} ({len(s0)} leaves bitwise)")
        return s1

    cfg = registry.get_smoke_config("llama3.2-1b")

    # the ZeRO-1 MRD mode across every DP-extent class (p2, odd, prime)
    for dp in (2, 3, 5, 8):
        compare("mrd_zero1", dp, cfg)

    # the other converted modes: one non-power-of-two + one power-of-two
    for mode in ("mrd_paper", "mrd_leaf"):
        for dp in (3, 8):
            compare(mode, dp, cfg)

    # compressed: EF residual must carry identically through the overlap path
    for dp in (3, 8):
        s = compare("compressed", dp, cfg)
        ef = [v for k, v in s.items() if "'ef'" in k]
        assert ef, "compressed state has no EF residual leaf"
        assert any(np.any(v != 0) for v in ef), (
            "EF residual never populated — carry lost")

    # bf16 params: the dtype-split bucket layout under overlap
    cfg_bf16 = registry.override(cfg, param_dtype="bfloat16")
    compare("mrd_zero1", 5, cfg_bf16, tag="-bf16")

    print("ALL-OVERLAP-DIFF-PASSED")
    """
)


@pytest.mark.slow
def test_overlap_vs_baseline_train_step():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout[-4000:]}\nSTDERR:\n{proc.stderr[-6000:]}"
    )
    assert "ALL-OVERLAP-DIFF-PASSED" in proc.stdout
