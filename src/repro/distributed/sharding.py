"""Divisibility-aware sharding rules: logical axes -> mesh axes per arch.

Mesh axes: ``("pod", "data", "model")`` multi-pod or ``("data", "model")``
single-pod.  ``("pod","data")`` form the DP/FSDP domain, ``"model"`` is TP.

Per-arch decisions are *derived*, not hand-written:
- attention activations shard over heads iff ``n_heads % tp == 0`` (else the
  head dims stay replicated and TP lives in the flattened QKV projections +
  MLP; decode caches then shard sequence over "model");
- MoE expert dim shards over the FSDP axis iff ``n_experts %  fsdp == 0``
  (true EP, llama4: 16e/16) else experts replicate and d_ff shards over TP;
- every weight matmul dim shards only when divisible.

Models stay distribution-agnostic: they call :func:`constrain` with logical
axis names; an active :class:`ShardingContext` maps them to mesh axes (no-op
outside a context, e.g. unit tests).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    dp_axes: tuple[str, ...]  # ("pod","data") or ("data",)
    fsdp_axis: Optional[str]  # weight/opt-state sharding over DP ("data")
    tp_axis: Optional[str]
    attn_heads_sharded: bool
    kv_heads_sharded: bool
    ep: bool  # expert dim over fsdp axis

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis] if self.tp_axis else 1

    @property
    def dp(self) -> int:
        out = 1
        for a in self.dp_axes:
            out *= self.mesh.shape[a]
        return out

    def fsdp_if(self, dim: int):
        if self.fsdp_axis and dim % self.mesh.shape[self.fsdp_axis] == 0:
            return self.fsdp_axis
        return None

    def tp_if(self, dim: int):
        if self.tp_axis is None:
            return None
        return self.tp_axis if dim % self.tp == 0 else None

    def batch_axes(self, batch_dim: int):
        """DP axes for a batch dim, or None when indivisible (e.g. B=1)."""
        if not self.dp_axes:
            return None
        return self.dp_axes if batch_dim % self.dp == 0 else None

    def manual_region(self) -> "ShardingRules":
        """Rules for code running *inside* a shard_map manual over the DP
        axes: batch dims are already local (no DP constraints allowed); TP
        constraints on the auto 'model' axis remain valid."""
        return dataclasses.replace(self, dp_axes=(), fsdp_axis=None)


def make_rules(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    fsdp: bool = True,
    tp_axis: str = "model",
) -> ShardingRules:
    axes = list(mesh.axis_names)
    if tp_axis not in axes:
        tp_axis = None  # pure-DP mesh (e.g. elastic non-p2 groups)
    dp_axes = tuple(a for a in axes if a != tp_axis)
    fsdp_axis = "data" if (fsdp and "data" in axes) else None
    tp = mesh.shape[tp_axis] if tp_axis else 1
    heads_ok = tp_axis is not None and cfg.n_heads > 0 and cfg.n_heads % tp == 0
    kv_ok = tp_axis is not None and cfg.n_kv_heads > 0 and cfg.n_kv_heads % tp == 0
    ep = (
        cfg.n_experts > 0
        and fsdp_axis is not None
        and cfg.n_experts % mesh.shape[fsdp_axis] == 0
    )
    return ShardingRules(
        mesh=mesh,
        dp_axes=dp_axes,
        fsdp_axis=fsdp_axis,
        tp_axis=tp_axis,
        attn_heads_sharded=heads_ok,
        kv_heads_sharded=kv_ok,
        ep=ep,
    )


# ---------------------------------------------------------------------------
# Param specs (path-based; stacked leading dims get None)
# ---------------------------------------------------------------------------


def _leaf_spec(name: str, shape, cfg: ModelConfig, r: ShardingRules) -> P:
    """Spec for the logical (unstacked) trailing dims of a param leaf."""
    t, f = r.tp_if, r.fsdp_if

    def pad(spec_tail):
        lead = len(shape) - len(spec_tail)
        return P(*([None] * lead), *spec_tail)

    if name == "embed":
        return pad((t(shape[-2]), None))
    if name == "lm_head":
        return pad((None, t(shape[-1])))
    if name in ("patch_proj", "frame_proj", "router"):
        return pad((None, None))
    if name in ("wq", "wk", "wv"):
        return pad((f(shape[-2]), t(shape[-1])))
    if name in ("bq", "bk", "bv"):
        return pad((t(shape[-1]),))
    if name == "wo":
        return pad((t(shape[-2]), f(shape[-1])))
    if name in ("w1", "w3"):
        if len(shape) >= 3 and cfg.n_experts:  # [.., E, d, f]
            if r.ep:
                return pad((r.fsdp_axis, None, t(shape[-1])))
            return pad((None, f(shape[-2]), t(shape[-1])))
        return pad((f(shape[-2]), t(shape[-1])))
    if name == "w2":
        if len(shape) >= 3 and cfg.n_experts:  # [.., E, f, d]
            if r.ep:
                return pad((r.fsdp_axis, t(shape[-2]), None))
            return pad((None, t(shape[-2]), f(shape[-1])))
        return pad((t(shape[-2]), f(shape[-1])))
    # --- ssm ---
    if name == "in_proj":  # mamba1 [d, 2*di]; split at di is shard-aligned
        return pad((f(shape[-2]), t(shape[-1])))
    if name in ("in_z", "in_x"):
        return pad((f(shape[-2]), t(shape[-1])))
    if name in ("in_bc", "in_dt"):
        return pad((f(shape[-2]), None))
    if name == "x_proj":
        return pad((t(shape[-2]), None))
    if name == "dt_proj":
        return pad((None, t(shape[-1])))
    if name == "out_proj":
        return pad((t(shape[-2]), f(shape[-1])))
    if name == "A_log":
        if len(shape) >= 2 and shape[-1] == cfg.ssm_state:  # mamba1 [di, st]
            return pad((t(shape[-2]), None))
        return pad((None,))  # mamba2 [nh]
    if name in ("conv_w", "conv_b", "dt_bias", "D", "norm_w"):
        # conv weights/small vectors: replicate (mamba2 conv spans mixed dims)
        if name == "D" and len(shape) >= 1 and shape[-1] == cfg.d_inner:
            return pad((t(shape[-1]),))
        return P(*([None] * len(shape)))
    # norms and everything else: replicated
    return P(*([None] * len(shape)))


def param_specs(cfg: ModelConfig, rules: ShardingRules, params: Any):
    """PartitionSpec pytree matching ``params`` (arrays or ShapeDtypeStructs)."""

    def spec_for(path, leaf):
        name = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        return _leaf_spec(name or "", leaf.shape, cfg, rules)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def param_shardings(cfg, rules, params):
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, s), param_specs(cfg, rules, params)
    )


# ---------------------------------------------------------------------------
# Activation constraint context (used inside model code via `constrain`)
# ---------------------------------------------------------------------------

_ACTIVE: list["ShardingContext"] = []


@dataclasses.dataclass
class ShardingContext:
    cfg: ModelConfig
    rules: ShardingRules

    def spec_for(self, kind: str, x) -> Optional[P]:
        r = self.rules
        if kind == "tokens":  # [B, S, d]
            return P(r.batch_axes(x.shape[0]), None, None)
        if kind == "mb_batch":  # [mb, B_mb, ...]: shard the per-microbatch batch
            return P(None, r.batch_axes(x.shape[1]), *([None] * (x.ndim - 2)))
        if kind == "q":  # [B, S, H, hd]
            h = r.tp_axis if r.attn_heads_sharded else None
            return P(r.batch_axes(x.shape[0]), None, h, None)
        if kind in ("k", "v"):  # [B, S, KV(_eff), hd] — divisibility on the
            # actual (possibly kv-repeated) head count
            h = r.tp_if(x.shape[2]) if r.attn_heads_sharded else None
            return P(r.batch_axes(x.shape[0]), None, h, None)
        if kind in ("cache_k", "cache_v"):  # [B, W, KV, hd]
            if r.kv_heads_sharded:
                return P(r.batch_axes(x.shape[0]), None, r.tp_axis, None)
            return P(r.batch_axes(x.shape[0]), r.tp_axis, None, None)
        if kind == "ffn":  # [B, S, f]
            return P(r.batch_axes(x.shape[0]), None, r.tp_if(x.shape[-1]))
        if kind == "expert_buf":  # [G, E, C, d]: groups over DP
            return P(r.batch_axes(x.shape[0]), None, None, None)
        if kind == "expert_buf_ep":  # [G, E, C, d]: experts over the EP axis.
            # Resharding expert_buf -> expert_buf_ep is exactly the token
            # all_to_all of true expert parallelism: tokens travel to the
            # expert-owning shards and the (huge) expert weights never move.
            if r.ep and x.shape[1] % r.mesh.shape[r.fsdp_axis] == 0:
                return P(None, r.fsdp_axis, None, None)
            return P(r.batch_axes(x.shape[0]), None, None, None)
        if kind == "ssm_inner":  # [B, S, di] or [B, di, ...]
            return P(r.batch_axes(x.shape[0]), None, r.tp_if(x.shape[2]) if x.ndim > 2 else None)
        if kind == "logits":  # [B, S, V]
            return P(r.batch_axes(x.shape[0]), None, r.tp_if(x.shape[-1]))
        return None


@contextlib.contextmanager
def sharding_ctx(cfg: ModelConfig, rules: ShardingRules):
    ctx = ShardingContext(cfg, rules)
    _ACTIVE.append(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.pop()


def kv_repeat_factor(H: int, KV: int) -> int:
    """Smallest factor r such that KV*r divides the TP axis cleanly (and r
    divides H/KV), enabling head-sharded GQA when KV < tp.  The (KV, rep)
    grouped reshape otherwise forces GSPMD to replicate attention
    intermediates (a multi-GB transient at 32k prefill)."""
    if not _ACTIVE:
        return 1
    r = _ACTIVE[-1].rules
    if r.tp_axis is None or H == 0 or H % r.tp:
        return 1
    if KV % r.tp == 0:
        return 1
    rep = H // KV
    for f in range(2, rep + 1):
        if rep % f == 0 and (KV * f) % r.tp == 0:
            return f
    return 1


def constrain(x, kind: str):
    """Apply a with_sharding_constraint if a ShardingContext is active."""
    if not _ACTIVE:
        return x
    ctx = _ACTIVE[-1]
    spec = ctx.spec_for(kind, x)
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(ctx.rules.mesh, spec)
        )
    except (TypeError, ValueError):
        # ValueError: indivisible shape for this spec — leave to GSPMD.
        # TypeError: eager (op-by-op) execution outside jit, where the
        # constraint is a no-op hint anyway (dispatch-regime benchmarks).
        return x
