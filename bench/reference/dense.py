"""Plain float32 reference of a dense decoder, the weights it shares with
the program under test, and the counts of operations and bytes that the
readers divide by: what an LLM configuration with ``"reference": "dense"``
gives the harness (``bench.spec.reference``).

- :func:`program_config`: the program's ``ModelConfig`` fields for the
  configuration file (the caller builds it, so this imports nothing of
  the program);
- :func:`make_weights`, :func:`served_gaps` and :data:`control`, the
  rounding of the lower-precision control;
- :func:`span_flops` and :func:`decode_bytes`, from ``bench/flops.py``'s
  dense formulas.

Equations (those of the program's dense path; the departures from the
published MiniCPM are listed in its configuration file under
``departures``):

    x     = embed[token]
    layer:  h = rms(x) * (1 + ln1);  q, k, v = h Wq, h Wk, h Wv
            q, k <- rotary(q, k, position, theta)   (half rotation)
            x += softmax(q k^T / sqrt(head_dim), causal) v  Wo
            h = rms(x) * (1 + ln2);  x += (silu(h W1) * (h W3)) W2
    logits = (rms(x) * (1 + final_norm)) embed^T          (tied head)

with ``rms(x) = x / sqrt(mean(x^2) + norm_eps)``.  Everything is computed in
float32 with ``jax.default_matmul_precision("highest")``, one sequence at a
time and one layer at a time (a scan over the stacked weights), and the
logits in blocks of rows, so that it fits beside nothing but the weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops


def program_config(c: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` for a
    configuration file."""
    m = c["model"]
    return dict(
        name=c["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        tie_embeddings=m["tie_embeddings"], param_dtype=m["dtype"],
        compute_dtype=m["dtype"],
    )


def span_flops(m: dict, start: int, stop: int, *, head_from: int, run=None) -> float:
    """Operations for the tokens at positions ``start .. stop - 1``, the
    output head counted from ``head_from`` on.  ``run`` (a ``RunData``) is
    there for counts that depend on what the program did, such as routing;
    a dense count needs only the shapes."""
    return flops.span_flops(m, start, stop, head_from=head_from)


def decode_bytes(m: dict, ticks: int, positions: int, run=None) -> int:
    """Least bytes that ``ticks`` decode ticks read, whose active slots
    attend ``positions`` positions in all: every weight once a tick, and
    the K/V rows of each position attended."""
    return flops.weight_bytes(m) * ticks + flops.kv_bytes_per_token(m) * positions


def _dtype(m: dict):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m["dtype"]]


def weight_shapes(m: dict) -> dict:
    d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
    H, KV, f, V = m["n_heads"], m["n_kv_heads"], m["d_ff"], m["vocab"]
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": {
            "attn": {
                "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
                "wv": (L, d, KV * hd), "wo": (L, H * hd, d),
            },
            "ln1": (L, d),
            "ln2": (L, d),
            "mlp": {"w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d)},
        },
    }


def _std(path: str, shape) -> float:
    if path == "embed":
        return 0.02
    if path.endswith(("norm", "ln1", "ln2")):
        return 0.1  # (1 + w) scales near 1
    return 1.0 / np.sqrt(shape[-2])  # fan-in of a [.., in, out] matrix


def key_of(seed: int):
    """A JAX key from any whole-number seed (all its bits count)."""
    words = np.random.SeedSequence(int(seed) & (2**128 - 1)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_weights(m: dict, seed: int):
    """Seeded random weights in the type they are served in, made on the
    device in one jitted call."""
    shapes = weight_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    dt = _dtype(m)

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = [
            (jax.random.normal(k, shape, jnp.float32) * _std(n, shape)).astype(dt)
            for k, n, (_, shape) in zip(keys, names, flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key_of(seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rotary(x, pos, theta):
    """x: [T, heads, hd]; rotate the two halves by position * frequency."""
    hd = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    a, b = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def identity(x, axis=-1):
    return x


def fp8(x, axis=-1):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (the
    lower-precision control's arithmetic for matmul operands)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# the control: the reference with float8 matmul operands, the precision
# below the bfloat16 that the configuration serves in
control = fp8


def hidden_states(m: dict, w, tokens, rnd=identity):
    """Final normed hidden states ``[T, d]`` of one sequence.  ``rnd``
    rounds every matmul operand (identity = the float32 reference)."""
    eps, theta = m["norm_eps"], m["rope_theta"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    mm = lambda a, b: rnd(a, -1) @ rnd(f32(b), 0)  # noqa: E731
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lw):
        h = _rms(x, f32(lw["ln1"]), eps)
        q = mm(h, lw["attn"]["wq"]).reshape(T, H, hd)
        k = mm(h, lw["attn"]["wk"]).reshape(T, KV, hd)
        v = mm(h, lw["attn"]["wv"]).reshape(T, KV, hd)
        q, k = _rotary(q, pos, theta), _rotary(k, pos, theta)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", p, v).reshape(T, H * hd)
        x = x + mm(o, lw["attn"]["wo"])
        h = _rms(x, f32(lw["ln2"]), eps)
        g = jax.nn.silu(mm(h, lw["mlp"]["w1"])) * mm(h, lw["mlp"]["w3"])
        return x + mm(g, lw["mlp"]["w2"]), None

    x = f32(w["embed"])[tokens]
    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, f32(w["final_norm"]), eps)


def _served_gaps(m, w, tokens, served, rows, rnd, block):
    """Per row ``i`` of ``rows``: the float32 reference's best logit minus
    its logit of the token chosen there (``served[i]``, or the token that
    ``rnd``'s arithmetic puts first when ``served`` is None)."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(m, w, tokens)[rows]
        h_low = hidden_states(m, w, tokens, rnd)[rows] if served is None else None
        emb = w["embed"].astype(jnp.float32)
        gaps = []
        for i in range(0, rows.shape[0], block):
            lg = h[i : i + block] @ emb.T
            if served is None:
                pick = jnp.argmax(rnd(h_low[i : i + block], -1) @ rnd(emb, -1).T, -1)
            else:
                pick = served[i : i + block]
            best = jnp.max(lg, -1)
            gaps.append(best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])
        return jnp.concatenate(gaps)


def served_gaps(m: dict, w, prompt, output, *, seq_len: int, rows_len: int,
                rnd=None, block: int = 256):
    """Logit gaps of a served request: for each served token, how far the
    reference's logit of that token lies below the reference's best at its
    position.  With ``rnd`` the served tokens are replaced by those that
    ``rnd``'s arithmetic puts first (the lower-precision control).

    The sequence is padded at its end to ``seq_len`` and the rows to
    ``rows_len`` (causal attention leaves earlier positions unchanged), so
    one compiled program serves every request of a traffic mix."""
    prompt = np.asarray(prompt, np.int32)
    output = np.asarray(output, np.int32)
    seq = np.concatenate([prompt, output[:-1]])
    n = output.shape[0]
    if seq.shape[0] > seq_len or n > rows_len:
        raise ValueError(f"request of {seq.shape[0]} tokens, {n} served, "
                         f"exceeds the padded {seq_len}/{rows_len}")
    seq_p = np.zeros((seq_len,), np.int32)
    seq_p[: seq.shape[0]] = seq
    rows = np.full((rows_len,), prompt.shape[0] - 1, np.int32)
    rows[:n] = np.arange(prompt.shape[0] - 1, seq.shape[0])
    out_p = np.zeros((rows_len,), np.int32)
    out_p[:n] = output
    gaps = _served_gaps_jit(
        _Frozen(m), w, jnp.asarray(seq_p), None if rnd else jnp.asarray(out_p),
        jnp.asarray(rows), rnd or identity, block,
    )
    return np.asarray(gaps)[:n]


_served_gaps_jit = jax.jit(_served_gaps, static_argnums=(0, 5, 6))


class _Frozen(dict):
    """A hashable view of a configuration dict (a static jit argument)."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))
