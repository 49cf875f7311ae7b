"""The plain references agree with the program's path at smoke size: the
paged prefill then decode logits of the dense decoder, and the operator
and certified solutions of D-iteration PageRank."""

import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.kinds import llm_paged  # noqa: E402
from bench.reference import dense  # noqa: E402
from bench.reference.pagerank import PageRank  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def tiny_config(dtype="float32"):
    c = json.loads((ROOT / "bench/configs/minicpm-2b.json").read_text())
    c = copy.deepcopy(c)
    c["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      head_dim=16, d_ff=128, vocab=256, token_ids_below=250,
                      dtype=dtype, dtype_bytes=4 if dtype == "float32" else 2)
    return c


def reference_logits(m, w, seq):
    with jax.default_matmul_precision("highest"):
        h = dense.hidden_states(m, w, jnp.asarray(seq))
        return np.asarray(h @ w["embed"].astype(jnp.float32).T)


def test_paged_prefill_then_decode_matches_the_reference():
    from repro.distributed import serve as dserve
    from repro.launch.train import build_mesh

    c = tiny_config()
    m = c["model"]
    cfg = llm_paged.model_config(c, dense)
    w = dense.make_weights(m, seed=2**35 + 1)
    llm_paged._check_layout(cfg, w)
    mesh = build_mesh(1, 1)
    bs, max_len, lmax, slots = 8, 32, 8, 2
    from repro.serving import make_workload

    wl = make_workload("llm_decode_paged", cfg=cfg, mesh=mesh, params=w,
                       slots=slots, max_len=max_len, max_prompt_len=lmax,
                       block_size=bs)
    st = wl.pool.state
    prefill, _ = dserve.make_paged_slot_prefill_step(cfg, mesh, lmax, max_len, bs)
    decode, _ = dserve.make_paged_pool_decode_step(cfg, mesh, bs)
    rng = np.random.default_rng(0)
    plen, steps = 5, 10
    seq = rng.integers(0, 250, size=plen + steps).astype(np.int32)
    prompt = np.zeros(lmax, np.int32)
    prompt[:plen] = seq[:plen]
    nb = max_len // bs
    row = jnp.arange(1, nb + 1, dtype=jnp.int32)
    with mesh:
        last, pages, tables, sl = jax.jit(prefill)(
            w, jnp.asarray(prompt), jnp.int32(plen), st["pages"], st["tables"],
            st["slot"], jnp.int32(0), row, jnp.ones(nb, bool))
        got = [np.asarray(last)]
        jd = jax.jit(decode)
        for i in range(steps - 1):
            toks = jnp.asarray([seq[plen + i], 0], jnp.int32)
            lens = jnp.asarray([plen + i, 0], jnp.int32)
            lg, pages, sl = jd(w, toks, pages, tables, sl, lens,
                               jnp.asarray([True, False]))
            got.append(np.asarray(lg[0]))
    want = reference_logits(m, w, seq[: plen + steps - 1])[plen - 1:]
    got = np.stack(got)
    scale = float(np.abs(want).max())
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_served_gaps_of_the_reference_itself_are_zero():
    c = tiny_config()
    m = c["model"]
    w = dense.make_weights(m, seed=3)
    prompt = np.arange(1, 7, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):  # greedy continuation by the reference
        seq.append(int(np.argmax(reference_logits(m, w, np.asarray(seq))[-1])))
    out = np.asarray(seq[6:], np.int32)
    gaps = dense.served_gaps(m, w, prompt, out, seq_len=16, rows_len=8)
    assert gaps.shape == (6,)
    assert np.abs(gaps).max() <= 1e-4
    bad = out.copy()
    bad[2] = (bad[2] + 1) % 256
    assert dense.served_gaps(m, w, prompt, bad, seq_len=16, rows_len=8)[2] > 1e-3


def test_fp8_rounding():
    x = jnp.asarray([[1.0, 0.3, -0.0071], [448.0, 1e-3, 2.0]])
    y = np.asarray(dense.fp8(x))
    assert np.allclose(y, np.asarray(x), rtol=0.07, atol=1e-2)
    assert not np.array_equal(y, np.asarray(x))
    assert dense.identity(x) is x


def test_weights_repeat_from_the_seed():
    m = tiny_config("bfloat16")["model"]
    a = dense.make_weights(m, seed=2**40 + 9)
    b = dense.make_weights(m, seed=2**40 + 9)
    c = dense.make_weights(m, seed=9)  # low bits alike, seed differs
    eq = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(eq))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
    assert a["embed"].dtype == jnp.bfloat16


@pytest.mark.parametrize("out_degree", [4, 16])
@pytest.mark.parametrize("seed", [0, 2**40 + 3])
def test_pagerank_operator_matches_the_program(seed, out_degree):
    from repro.asynchrony.solvers import make_solver

    n = 30
    prog = make_solver("d_iteration", n=n, damping=0.85, out_degree=out_degree,
                       seed=seed)
    ref = PageRank(n, damping=0.85, out_degree=out_degree, seed=seed)
    x = np.random.default_rng(1).random(n)
    want = ref.apply(x) + 0.15 / n
    got = np.asarray(prog.full_map(jnp.asarray(x, jnp.float32)), np.float64)
    assert np.abs(got - want).max() < 1e-6
    # column sums of damping * P are damping: mass is conserved
    assert np.allclose(np.bincount(ref.cols, ref.vals, minlength=n), 0.85)


def test_pagerank_bfloat16_control_misses_eps():
    n, eps = 60, 1e-7
    ref = PageRank(n, damping=0.85, out_degree=4, seed=2)
    v = np.random.default_rng(3).random(n)
    v /= v.sum()
    x64 = ref.solve_rounded(v, eps, 2000, np.float64)
    assert ref.residual(x64, v) <= eps
    x16 = ref.solve_rounded(v, eps, 2000, ml_dtypes.bfloat16)
    assert ref.residual(x16, v) > 10 * eps
