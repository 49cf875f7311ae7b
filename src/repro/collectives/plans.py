"""Layer 4 of the collectives subsystem: *plans*.

A :class:`CollectivePlan` binds one choice from each lower layer —
schedule (``repro.collectives.schedules``), executor
(``repro.collectives.executors``), payload transform
(``repro.collectives.transforms``) — plus a reduction op, to a concrete
communication domain: named mesh axes (device executors, called inside
``shard_map``) or a stacked rank count ``p`` (sim executor).

Every collective in the repo — blocking or non-blocking, compressed or
plain, single- or multi-axis — executes through this one stage
interpreter, so there is exactly one code path to validate:

- :meth:`CollectivePlan.run` executes all stages of all phases (blocking).
- :meth:`CollectivePlan.init` / :meth:`CollectivePlan.step` expose the
  paper's non-blocking state machine (Fig. 4): each ``step`` call
  advances **one** communication stage via ``lax.switch`` over a stage
  counter carried in a pytree; a cycle completes after
  :meth:`cycle_length` calls, sets ``flag``, publishes the reduced
  value, and re-latches the caller's current local contribution
  ("each cycle begins with the backward shift").

Chained (multi-axis) plans concatenate per-axis stage lists, which is
how non-power-of-two DP domains and ``("pod","data")`` meshes run the
same code path as a single axis.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.collectives import transforms as T
from repro.collectives.executors import make_backend, resolve_op
from repro.collectives.schedules import Phase, Stage, get_schedule, pivot

# ---------------------------------------------------------------------------
# The one stage interpreter (all backends, all transforms, all stage kinds)
#
# Each stage is split into a *start* half (encode the outgoing payload and
# issue the permute) and a *finish* half (fold the received payload in).
# Blocking execution composes the two back-to-back; the bucketed engine
# (:meth:`CollectivePlan.run_buffers`) interleaves them stage-major across
# buckets so a bucket's permute is in flight while its neighbours run
# their encode/combine compute (DESIGN.md S10).
# ---------------------------------------------------------------------------


def _stage_start(x, st: Stage, be, tf):
    """Issue the stage's communication; returns the in-flight context.

    Reducing stages (``bshift``/``butterfly``/``rs``) send
    ``tf.encode``-ed payloads; copy stages (``fshift``/``ag``) always
    move raw buffers.
    """
    if st.kind in ("bshift", "butterfly"):
        payload = tf.encode(x, be)
        return x, tuple(be.permute(leaf, st.pairs) for leaf in payload)
    if st.kind == "fshift":
        return x, be.permute(x, st.pairs)
    if st.kind == "rs":
        d = st.distance
        lower, upper = be.split_half(x)
        my_bit = (be.rank() & d) != 0
        to_send = be.where(my_bit, lower, upper)
        keep = be.where(my_bit, upper, lower)
        payload = tf.encode(to_send, be)
        return keep, tuple(be.permute(leaf, st.pairs) for leaf in payload)
    if st.kind == "ag":
        return x, be.permute(x, st.pairs)
    raise ValueError(f"bad stage kind {st.kind}")


def _stage_finish(ctx, st: Stage, be, p: int, op: Callable, tf):
    """Fold the in-flight payload from :func:`_stage_start` back in."""
    p0, _, extra = pivot(p)
    r = be.rank()
    if st.kind in ("bshift", "butterfly"):
        x, recv = ctx
        # butterfly partners both hold the stage result, so each must combine
        # the *canonical* (wire-roundtripped) views — otherwise a lossy
        # transform leaves the two ranks with slightly different values and
        # the allreduce contract (all ranks equal) silently breaks.
        keep = tf.canonicalize(x, be) if st.kind == "butterfly" else x
        combined = tf.combine(keep, recv, op, be)
        pred = (r < extra) if st.kind == "bshift" else (r < p0)
        return be.where(pred, combined, x)
    if st.kind == "fshift":
        x, recv = ctx
        return be.where(r >= p0, recv, x)
    if st.kind == "rs":
        keep, recv = ctx
        combined = tf.combine(keep, recv, op, be)
        return be.where(r < p0, combined, keep)
    if st.kind == "ag":
        x, recv = ctx
        my_bit = (r & st.distance) != 0
        return be.where(my_bit, be.concat(recv, x), be.concat(x, recv))
    raise ValueError(f"bad stage kind {st.kind}")


def exec_stage(x, st: Stage, be, p: int, op: Callable, tf=None):
    """Apply one schedule stage under backend ``be`` with transform ``tf``
    (start and finish back-to-back — the blocking composition)."""
    tf = tf or T.IdentityTransform()
    return _stage_finish(_stage_start(x, st, be, tf), st, be, p, op, tf)


def _run_phase(x, collective: str, be, p: int, op: Callable, tf):
    if p == 1:
        return x
    for st in Phase(collective, 0).stages(p):
        x = exec_stage(x, st, be, p, op, tf if collective != "allgather" else None)
    return x


# ---------------------------------------------------------------------------
# Live-plan tracking (elastic resize invalidation hook, DESIGN.md S12)
#
# Plans memoize derived state (backends, bound stage tables) per instance.
# A mesh resize changes axis sizes out from under long-lived plan objects;
# the elastic runtime calls invalidate_all_plans() at each ResizeEvent so
# every live plan rebuilds its derivations on next use.  A plain weakref
# list (not a WeakSet — frozen-dataclass equality would collapse distinct
# instances with equal fields) tracks liveness without pinning plans.
# ---------------------------------------------------------------------------

_LIVE_PLANS: list = []
_PRUNE_THRESHOLD = 256


def _track_plan(plan) -> None:
    global _PRUNE_THRESHOLD
    _LIVE_PLANS.append(weakref.ref(plan))
    # amortized prune: long-running non-elastic workloads construct plans
    # indefinitely and never call invalidate_all_plans(), so dead refs
    # must not accumulate unboundedly
    if len(_LIVE_PLANS) >= _PRUNE_THRESHOLD:
        _LIVE_PLANS[:] = [r for r in _LIVE_PLANS if r() is not None]
        _PRUNE_THRESHOLD = max(256, 2 * len(_LIVE_PLANS))


def live_plans() -> list:
    """Currently alive CollectivePlan instances (prunes dead refs)."""
    alive = []
    kept = []
    for ref in _LIVE_PLANS:
        p = ref()
        if p is not None:
            alive.append(p)
            kept.append(ref)
    _LIVE_PLANS[:] = kept
    return alive


def invalidate_all_plans() -> int:
    """Invalidate every live plan's memoized derivations (mesh resize
    hook).  Returns the number of plans invalidated."""
    plans_alive = live_plans()
    for p in plans_alive:
        p.invalidate()
    return len(plans_alive)


# ---------------------------------------------------------------------------
# CollectivePlan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectivePlan:
    """schedule x executor x transform x op, bound to axes (device) or p (sim).

    ``phases`` defaults to the registered decomposition of ``schedule``;
    pass it explicitly for primitive plans (a bare reduce-scatter or
    all-gather, see :func:`reduce_scatter_plan` / :func:`allgather_plan`).
    """

    schedule: str = "mrd"
    op: Any = "sum"  # 'sum' | 'max' | 'min' | callable
    transform: Any = "identity"  # name | transform instance
    executor: str = "device"  # 'device' | 'device_fused' | 'sim'
    axes: Optional[tuple[str, ...]] = None  # device: mesh axis names (chained)
    p: Optional[int] = None  # sim: stacked rank count
    phases: Optional[tuple[Phase, ...]] = None
    transform_kwargs: tuple = ()  # e.g. (('block', 128),)

    def __post_init__(self):
        if (self.axes is None) == (self.p is None):
            raise ValueError("bind exactly one of axes= (device) or p= (sim)")
        if self.p is not None and self.executor == "device":
            object.__setattr__(self, "executor", "sim")
        if self.axes is not None and isinstance(self.axes, str):
            object.__setattr__(self, "axes", (self.axes,))
        self._transform().validate_op(self.op)
        _track_plan(self)

    # -- layer resolution ---------------------------------------------------
    #
    # A frozen dataclass is memoizable: schedule construction, transform
    # resolution, and backend instantiation are cached per instance (in
    # ``__dict__``, invisible to dataclass eq/hash) so ``step()``/``run()``
    # don't rebuild them on every trace.  Anything depending on *device*
    # axis sizes is keyed by the resolved sizes, since the same plan object
    # may be traced under meshes of different shapes.

    def _memo(self, key, build):
        memo = self.__dict__.get("_memo_cache")
        if memo is None:
            memo = {}
            object.__setattr__(self, "_memo_cache", memo)
        if key not in memo:
            memo[key] = build()
        return memo[key]

    def invalidate(self):
        """Drop every memoized derivation (resolved backends, bound stage
        tables, cached permute specs) so the next use rebuilds against the
        current mesh/axis sizes.  The elastic runtime calls
        :func:`invalidate_all_plans` after a resize — device-axis plans
        re-resolve sizes per trace anyway (memo keys include the resolved
        sizes), so this is a hard guarantee plus a memory release for
        stage tables of extents that no longer exist."""
        self.__dict__.pop("_memo_cache", None)

    def _n_axes(self) -> int:
        return len(self.axes) if self.axes is not None else 1

    def _phases(self) -> tuple[Phase, ...]:
        if self.phases is not None:
            return self.phases
        return self._memo(
            "phases",
            lambda: tuple(get_schedule(self.schedule).phases(self._n_axes())),
        )

    def _transform(self):
        return self._memo(
            "transform",
            lambda: T.resolve_transform(
                self.transform, **dict(self.transform_kwargs)
            ),
        )

    def _backend(self, axis_index: int):
        if self.axes is not None:
            return self._memo(
                ("backend", axis_index),
                lambda: make_backend(self.executor, axis=self.axes[axis_index]),
            )
        return self._memo(
            ("backend", axis_index),
            lambda: make_backend(self.executor, p=self.p),
        )

    def _size(self, axis_index: int) -> int:
        """Static axis size; device sizes resolve inside the traced region."""
        if self.p is not None:
            return self.p
        return jax.lax.axis_size(self.axes[axis_index])

    def _sizes(self) -> tuple[int, ...]:
        return tuple(self._size(ph.axis_index) for ph in self._phases())

    # -- introspection ------------------------------------------------------

    def bound_stages(self) -> tuple[tuple[Stage, int, int], ...]:
        """Flat [(stage, axis_index, p)] across phases (allreduce plans)."""

        def build():
            out = []
            for ph in self._phases():
                if ph.collective != "allreduce":
                    raise ValueError(
                        "stage-at-a-time stepping needs an allreduce-only plan "
                        f"(schedule {self.schedule!r} has a {ph.collective} phase)"
                    )
                p = self._size(ph.axis_index)
                for st in ph.stages(p):
                    out.append((st, ph.axis_index, p))
            return tuple(out)

        return self._memo(("bound_stages", self._sizes()), build)

    def bound_stage_table(
        self,
    ) -> tuple[tuple[Stage, str, int, int], ...]:
        """Flat [(stage, collective, axis_index, p)] across *all* phases —
        the bucketed engine's iteration order (any phase kinds)."""

        def build():
            out = []
            for ph in self._phases():
                p = self._size(ph.axis_index)
                for st in ph.stages(p):
                    out.append((st, ph.collective, ph.axis_index, p))
            return tuple(out)

        return self._memo(("stage_table", self._sizes()), build)

    def cycle_length(self) -> int:
        """Non-blocking calls per completed reduction (>= 1)."""
        return max(len(self.bound_stages()), 1)

    def pad_quantum(self) -> int:
        """Required divisor of the (1-D) buffer length for this plan."""
        q = self._transform().quantum
        for ph in self._phases():
            if ph.collective == "reduce_scatter":
                q *= pivot(self._size(ph.axis_index))[0]
        return q

    # -- telemetry ----------------------------------------------------------

    def _emit_stage_telemetry(self, n_bufs: int, nbytes: int) -> None:
        """Per-stage trace events + message/byte counters (caller gates on
        ``obs.enabled()``).  Stage structure is static per (schedule, sizes)
        so this emits at *bind* time — inside jit that is trace time, the
        only honest place: the traced region cannot host-record per call.
        Message counts come straight from the schedule (``len(st.pairs)``),
        so summing ``coll.messages`` over one MRD cycle reproduces the
        paper's p0*mu0 + 2*(p - 2^floor(log2 p)) closed form — the
        extra-message prediction lands in ``coll.extra_msgs`` (the shift
        stages)."""
        total = extra = vol = 0.0
        for s_idx, (st, coll, _ai, p) in enumerate(self.bound_stage_table()):
            msgs = len(st.pairs) * n_bufs
            per_rank = nbytes / max(p, 1) if self.p is not None else nbytes
            stage_bytes = len(st.pairs) * st.payload_fraction * per_rank
            total += msgs
            vol += stage_bytes
            if st.kind in ("bshift", "fshift"):
                extra += msgs
            obs.instant(
                "coll.stage",
                schedule=self.schedule,
                stage=s_idx,
                kind=st.kind,
                collective=coll,
                p=p,
                distance=st.distance,
                msgs=msgs,
                payload_fraction=st.payload_fraction,
            )
        obs.counter("coll.messages", schedule=self.schedule).add(total)
        obs.counter("coll.extra_msgs", schedule=self.schedule).add(extra)
        obs.counter("coll.bytes", schedule=self.schedule).add(vol)
        obs.counter("coll.runs", schedule=self.schedule).add(1)

    # -- blocking execution -------------------------------------------------

    def run(self, x):
        """Execute all phases.  Allreduce-only plans accept a pytree; plans
        with reduce-scatter/all-gather phases take a single array (device:
        1-D local vector, sim: ``[p, n]`` stacked)."""
        if not obs.enabled():
            return self._run_impl(x)
        nbytes = sum(int(l.size) * l.dtype.itemsize for l in jax.tree.leaves(x))
        with obs.span(
            "coll.run",
            schedule=self.schedule,
            executor=self.executor,
            sizes=list(self._sizes()),
            nbytes=nbytes,
        ):
            out = self._run_impl(x)
        self._emit_stage_telemetry(1, nbytes)
        return out

    def _run_impl(self, x):
        op = resolve_op(self.op)
        tf = self._transform()
        phases = self._phases()
        ar_only = all(ph.collective == "allreduce" for ph in phases)
        if ar_only:
            for ph in phases:
                be = self._backend(ph.axis_index)
                p = self._size(ph.axis_index)
                if p == 1:
                    continue
                x = jax.tree.map(
                    lambda leaf: _run_phase(leaf, "allreduce", be, p, op, tf), x
                )
            return x
        for ph in phases:
            be = self._backend(ph.axis_index)
            p = self._size(ph.axis_index)
            if ph.collective == "reduce_scatter" and p > 1:
                ndim = 2 if self.p is not None else 1
                if x.ndim != ndim:
                    raise ValueError(
                        f"reduce-scatter phase needs a {ndim}-D buffer "
                        f"({'[p, n] stacked' if ndim == 2 else 'rank-local 1-D'}), "
                        f"got shape {x.shape}"
                    )
                n = x.shape[-1]
                quantum = pivot(p)[0] * tf.quantum
                if n % quantum:
                    raise ValueError(
                        f"reduce-scatter phase over p={p} needs len % {quantum} "
                        f"== 0 (p0 x transform quantum), got {n}"
                    )
            x = _run_phase(x, ph.collective, be, p, op, tf)
        return x

    # -- bucketed, pipelined execution (DESIGN.md S10) ----------------------

    def run_buffers(self, bufs: Sequence) -> list:
        """Execute this plan's stages **stage-major across buffers**.

        ``bufs`` are independent 1-D buffers (sim: ``[p, n]`` stacked) —
        typically the buckets of :func:`repro.collectives.buckets.pack`.
        For every stage, buffer *k*'s permute is issued before buffer
        *k+1*'s previous-stage combine runs, so XLA can overlap
        collective-permute with the neighbouring buffers' encode/combine
        compute and no more than one stage of payload per buffer is in
        flight.  Identical math to :meth:`run` per buffer — bit-identical
        for the identity transform.
        """
        bufs = list(bufs)
        if not obs.enabled():
            return self._run_buffers_impl(bufs)
        nbytes = sum(int(b.size) * b.dtype.itemsize for b in bufs)
        with obs.span(
            "coll.run_buffers",
            schedule=self.schedule,
            n_buffers=len(bufs),
            nbytes=nbytes,
        ):
            out = self._run_buffers_impl(bufs)
        if bufs:
            self._emit_stage_telemetry(len(bufs), nbytes)
        return out

    def _run_buffers_impl(self, bufs: list) -> list:
        table = self.bound_stage_table()
        if not table or not bufs:
            return bufs
        op = resolve_op(self.op)
        tf = self._transform()
        if any(coll == "reduce_scatter" for _, coll, _, _ in table):
            q = self.pad_quantum()
            for i, b in enumerate(bufs):
                if b.shape[-1] % q:
                    raise ValueError(
                        f"reduce-scatter phases need buffer len % {q} == 0 "
                        f"(pad_quantum), got {b.shape[-1]} for buffer {i}"
                    )
        ctxs: list = [None] * len(bufs)
        prev = None  # (stage, backend, p) whose permutes are in flight
        for st, _coll, ai, p in table:
            be = self._backend(ai)
            for k in range(len(bufs)):
                if prev is not None:
                    bufs[k] = _stage_finish(ctxs[k], *prev, op, tf)
                ctxs[k] = _stage_start(bufs[k], st, be, tf)
            prev = (st, be, p)
        return [_stage_finish(c, *prev, op, tf) for c in ctxs]

    def run_bucketed(self, tree, *, bucket_bytes=None, layout=None):
        """Allreduce a pytree in dtype-homogeneous, size-capped buckets.

        Leaves are packed by :mod:`repro.collectives.buckets` (dtypes are
        preserved end-to-end — a bf16 leaf travels and reduces as bf16),
        each bucket padded to :meth:`pad_quantum`, then all stages execute
        pipelined via :meth:`run_buffers`.  Pass ``layout`` to reuse a
        prebuilt :class:`~repro.collectives.buckets.BucketLayout`;
        otherwise one is derived from the tree (``bucket_bytes=None`` =
        one bucket per dtype).  Only allreduce-composition schedules
        (every registered ``SCHEDULES`` entry) preserve buffer lengths
        end-to-end, so primitive RS/AG plans are rejected.
        """
        from repro.collectives import buckets as B

        if self.phases is not None and not all(
            ph.collective == "allreduce" for ph in self.phases
        ):
            raise ValueError(
                "run_bucketed needs an allreduce-schedule plan (primitive "
                "reduce-scatter/all-gather plans change buffer lengths)"
            )
        if layout is None:
            layout = B.build_layout(
                tree,
                bucket_bytes=bucket_bytes,
                quantum=self.pad_quantum(),
                stacked=self.p,
            )
        bufs = B.pack(tree, layout)
        return B.unpack(self.run_buffers(bufs), layout)

    # -- late-admission pipelined execution (DESIGN.md S16) -----------------

    def pipeline(self) -> "BucketPipeline":
        """A :class:`BucketPipeline` over this plan — :meth:`run_buffers`
        generalized so buckets may be *admitted while earlier buckets are
        already in flight* (the ready-bucket grad-sync overlap path)."""
        return BucketPipeline(self)

    # -- non-blocking state machine (paper Fig. 4) --------------------------

    def init(self, value) -> dict[str, Any]:
        """Create the state machine's state, latching ``value`` as the first
        cycle's contribution.  ``value``: per-rank pytree (device) or
        ``[p, ...]`` stacked (sim)."""
        return {
            "stage": jnp.zeros((), jnp.int32),
            "buf": value,
            "result": jax.tree.map(jnp.zeros_like, value),
            "flag": jnp.zeros((), jnp.bool_),  # True for exactly the completing call
            "cycles": jnp.zeros((), jnp.int32),
        }

    def step(self, state: dict[str, Any], local_value) -> dict[str, Any]:
        """Advance the non-blocking collective by one stage.

        Returns the new state.  ``state['flag']`` is True iff this call
        completed a cycle; then ``state['result']`` holds the reduction of
        the values latched at that cycle's start.  ``local_value`` is
        latched only when a new cycle begins (stage == 0), matching the
        paper's statechart.
        """
        op = resolve_op(self.op)
        tf = self._transform()
        bound = self.bound_stages()
        nstages = len(bound)

        if nstages == 0:  # all axes size 1: every call is a complete cycle
            return {
                "stage": state["stage"],
                "buf": local_value,
                "result": local_value,
                "flag": jnp.ones((), jnp.bool_),
                "cycles": state["cycles"] + 1,
            }

        starting = state["stage"] == 0
        buf = jax.tree.map(
            lambda lv, b: jnp.where(starting, lv, b), local_value, state["buf"]
        )

        def _stage_fn(st, axis_index, p):
            be = self._backend(axis_index)

            def apply(b):
                return jax.tree.map(
                    lambda leaf: exec_stage(leaf, st, be, p, op, tf), b
                )

            return apply

        buf = jax.lax.switch(
            state["stage"], [_stage_fn(*b) for b in bound], buf
        )

        nxt = state["stage"] + 1
        done = nxt == nstages
        return {
            "stage": jnp.where(done, 0, nxt),
            "buf": buf,
            "result": jax.tree.map(
                lambda b, r: jnp.where(done, b, r), buf, state["result"]
            ),
            "flag": done,
            "cycles": state["cycles"] + done.astype(jnp.int32),
        }

    def run_blocking(self, value):
        """Drive the state machine through one full cycle (tests/reference)."""
        st = self.init(value)
        for _ in range(self.cycle_length()):
            st = self.step(st, value)
        return st["result"]


# ---------------------------------------------------------------------------
# BucketPipeline: run_buffers with late admission (DESIGN.md S16)
# ---------------------------------------------------------------------------


class BucketPipeline:
    """Stage-major pipelined execution with **late bucket admission**.

    :meth:`CollectivePlan.run_buffers` needs every bucket up front; the
    ready-bucket overlap path (gradsync ``overlap=True``) produces
    buckets *while earlier buckets are already mid-schedule* — bucket k's
    permutes must be in flight while the backward segments that feed
    buckets k+1..N are still tracing.  This class is the same stage
    interpreter (:func:`_stage_start` / :func:`_stage_finish`) with an
    explicit in-flight set:

    - :meth:`admit` packs a new bucket into the pipeline and issues its
      first stage's permute;
    - :meth:`advance` moves every in-flight bucket forward one stage
      (finish the received payload, issue the next permute) — call it
      between backward segments so the permutes overlap autodiff compute;
    - :meth:`drain` runs all remaining stages stage-major and returns
      the finished buffers.

    Per bucket the stage sequence is exactly ``run_buffers``'s, and every
    stage's math touches only that bucket's arrays, so results are
    **bit-identical** to ``run_buffers`` for any admission/advance
    interleaving — including for lossy transforms (int8 block grids are
    keyed to offsets within a bucket, which this never changes).
    """

    def __init__(self, plan: CollectivePlan):
        self.plan = plan
        self.table = plan.bound_stage_table()
        self._op = resolve_op(plan.op)
        self._tf = plan._transform()
        self._check_quantum = any(
            coll == "reduce_scatter" for _, coll, _, _ in self.table
        )
        self._q = plan.pad_quantum() if self._check_quantum else 1
        self._inflight: dict = {}  # key -> (stage index started, ctx)
        self._done: dict = {}

    def _start(self, buf, i: int):
        st, _coll, ai, _p = self.table[i]
        return _stage_start(buf, st, self.plan._backend(ai), self._tf)

    def _finish(self, ctx, i: int):
        st, _coll, ai, p = self.table[i]
        return _stage_finish(ctx, st, self.plan._backend(ai), p, self._op, self._tf)

    def admit(self, key, buf) -> None:
        """Enter ``buf`` into the pipeline under ``key`` and issue its
        first stage.  Plans with no stages (all axes size 1) complete
        immediately."""
        if key in self._inflight or key in self._done:
            raise ValueError(f"bucket {key!r} admitted twice")
        if self._check_quantum and buf.shape[-1] % self._q:
            raise ValueError(
                f"reduce-scatter phases need buffer len % {self._q} == 0 "
                f"(pad_quantum), got {buf.shape[-1]} for bucket {key!r}"
            )
        obs.instant(
            "coll.pipeline.admit",
            key=str(key),
            inflight=len(self._inflight) + 1,
            nbytes=int(buf.size) * buf.dtype.itemsize,
        )
        if not self.table:
            self._done[key] = buf
            return
        self._inflight[key] = (0, self._start(buf, 0))

    def advance(self) -> None:
        """Advance every in-flight bucket by one stage (admission order)."""
        for key in list(self._inflight):
            i, ctx = self._inflight[key]
            buf = self._finish(ctx, i)
            if i + 1 < len(self.table):
                self._inflight[key] = (i + 1, self._start(buf, i + 1))
            else:
                del self._inflight[key]
                self._done[key] = buf

    def drain(self) -> dict:
        """Run all remaining stages stage-major; returns {key: buffer}
        and resets the pipeline."""
        n = len(self._inflight) + len(self._done)
        with obs.span("coll.pipeline.drain", n_buckets=n):
            while self._inflight:
                self.advance()
            out, self._done = self._done, {}
        if obs.enabled() and out:
            nbytes = sum(int(b.size) * b.dtype.itemsize for b in out.values())
            self.plan._emit_stage_telemetry(len(out), nbytes)
        return out


# ---------------------------------------------------------------------------
# Plan factories
# ---------------------------------------------------------------------------


def allreduce_plan(
    *,
    schedule: str = "mrd",
    op: Any = "sum",
    transform: Any = "identity",
    executor: str = "device",
    axes: Optional[Sequence[str]] = None,
    p: Optional[int] = None,
    **transform_kwargs,
) -> CollectivePlan:
    return CollectivePlan(
        schedule=schedule,
        op=op,
        transform=transform,
        executor=executor,
        axes=tuple(axes) if axes is not None else None,
        p=p,
        transform_kwargs=tuple(sorted(transform_kwargs.items())),
    )


def reduce_scatter_plan(
    *,
    op: Any = "sum",
    transform: Any = "identity",
    executor: str = "device",
    axes: Optional[Sequence[str]] = None,
    p: Optional[int] = None,
    **transform_kwargs,
) -> CollectivePlan:
    """Chained recursive-halving reduce-scatter over ``axes`` (in order)."""
    n = len(axes) if axes is not None else 1
    return CollectivePlan(
        schedule="reduce_scatter",
        op=op,
        transform=transform,
        executor=executor,
        axes=tuple(axes) if axes is not None else None,
        p=p,
        phases=tuple(Phase("reduce_scatter", i) for i in range(n)),
        transform_kwargs=tuple(sorted(transform_kwargs.items())),
    )


def allgather_plan(
    *,
    executor: str = "device",
    axes: Optional[Sequence[str]] = None,
    p: Optional[int] = None,
) -> CollectivePlan:
    """Chained recursive-doubling all-gather (reverse axis order, the inverse
    of :func:`reduce_scatter_plan`)."""
    n = len(axes) if axes is not None else 1
    return CollectivePlan(
        schedule="allgather",
        executor=executor,
        axes=tuple(axes) if axes is not None else None,
        p=p,
        phases=tuple(Phase("allgather", i) for i in reversed(range(n))),
    )


def tree_allreduce(
    tree,
    *,
    schedule: str = "mrd",
    op: Any = "sum",
    transform: Any = "identity",
    executor: str = "device",
    axes: Sequence[str] = (),
    p: Optional[int] = None,
    bucket_bytes: Optional[int] = None,
    **transform_kwargs,
):
    """Allreduce a pytree in dtype-homogeneous buckets, chained over
    ``axes`` (device) or a stacked rank count ``p`` (sim).

    Runs through :meth:`CollectivePlan.run_bucketed`: leaf dtypes are
    preserved end-to-end (a bf16+fp32 tree no longer promotes to one fp32
    wire vector), and ``bucket_bytes`` caps each wire buffer so stages
    pipeline across buckets instead of materializing one flat gradient.
    ``rabenseifner`` is the default-worthy schedule for bandwidth-bound
    payloads like gradients; ``mrd`` for latency-bound.
    """
    if p is not None and axes:
        raise ValueError(
            "bind exactly one of axes= (device) or p= (sim), not both"
        )
    plan = allreduce_plan(
        schedule=schedule,
        op=op,
        transform=transform,
        executor=executor,
        axes=axes if p is None else None,
        p=p,
        **transform_kwargs,
    )
    return plan.run_bucketed(tree, bucket_bytes=bucket_bytes)
