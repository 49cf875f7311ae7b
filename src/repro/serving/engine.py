"""Layer 5 of the serving subsystem: the *engine* — a thin composition of
workload x scheduler x termination (mirroring ``repro.asynchrony.engine``).

Per engine tick:

1. **admit** — the scheduler maps (pending queue, free slots) to
   admissions; each admission is one jitted offset-prefill into a recycled
   slot (shapes fixed, never recompiles) and produces the request's first
   token (TTFT stops here);
2. **step** — one jitted pool step advances every active slot at its own
   cache offset;
3. **terminate/retire** — the termination protocol advances its staged MRD
   reduction one stage (the paper's non-blocking detection loop as serving
   control plane); slots certified done by the *agreed* result retire, are
   freed, and their outputs collected.

Metrics: TTFT / TPOT (wall seconds, p50/p95 in :meth:`ServeEngine.summary`),
token throughput, slot occupancy, plus deterministic tick-domain latencies
(queue wait, admission tick, retirement tick) for the bit-level tests.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.asynchrony.protocols import RES_INIT
from repro.runtime.elastic import ResizeEvent
from repro.runtime.policies import LoadSnapshot
from repro.serving.schedulers import get_scheduler
from repro.serving.termination import (
    TerminationConfig,
    get_termination,
    make_signals,
)


@dataclasses.dataclass
class Request:
    """One serving request (either a token prompt or a solver payload)."""

    id: int
    arrival: int = 0  # tick at which the request enters the queue
    prompt: Any = None  # llm_decode: 1-D int token array
    payload: Any = None  # fixedpoint_solve: affine payload [n] (None = default)
    max_new: int = 32  # generation budget / iteration budget
    eos: int = -1  # llm_decode: EOS token id (-1 = never)
    priority: int = 0  # 'priority' scheduler: higher first
    sla: Optional[int] = None  # TTFT SLA in ticks: deadline = arrival + sla
    eps: Optional[float] = None  # residual protocols: per-request threshold
    tenant: str = ""  # multi-tenant traffic model (serving/tenants.py)


@dataclasses.dataclass
class RequestResult:
    id: int
    output: np.ndarray  # token ids (trimmed) or solution vector
    arrival: int
    admit_tick: int
    retire_tick: int
    n_tokens: int
    certified: float  # agreed value at retirement (residual / done bit)
    converged: bool  # False only for budget-forced fixed-point retirement
    ttft_s: float
    tpot_s: float  # NaN for n_tokens <= 1 (no inter-token interval exists)
    queued_s: float  # admission start - submission (host clock)
    retries: int = 0  # capacity-forced requeues this request went through
    tenant: str = ""
    sla: Optional[int] = None
    sla_met: Optional[bool] = None  # TTFT tick deadline met (None = no SLA)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    scheduler: str = "fcfs"
    termination: str = "eos_maxlen"
    dp: int = 1  # termination-agreement replicas (MRD over [dp])
    eps: float = 1e-6
    window: int = 0  # residual_interval: 0 -> one agreement cycle + 1
    max_admit_per_tick: int = 0  # 0 = fill every free slot
    max_ticks: int = 100_000
    # capacity-forced requests (forced_at_capacity) get this many requeues
    # before retiring converged=False — 0 keeps the old fail-fast behavior
    max_retries: int = 0
    # ticks per fused dispatch: the device loop early-exits on the first
    # retiring tick (so retirement -> admission latency is one dispatch)
    # and the host caps it at the next pending arrival, so larger values
    # only amortize host overhead — they never delay scheduling decisions
    steps_per_dispatch: int = 16
    # multi-tenant admission quotas: {tenant name: max in-flight slots}
    # (0 / absent = unlimited); enforced at admission, so a tenant at its
    # quota is passed over and the slot goes to the next eligible request
    quotas: Any = None
    # capacity model for SLA autoscaling: each agreement replica funds
    # this many pool slots, so only min(slots, dp * slots_per_replica)
    # slots accept admissions — ServeEngine.resize() therefore changes
    # serving capacity, which is what the autoscaler trades against SLA
    # pressure.  None = every slot usable at any extent (the old model).
    slots_per_replica: Optional[int] = None


class ServeEngine:
    """Continuous-batching serving loop over a workload's pool."""

    def __init__(self, workload, cfg: ServeConfig = ServeConfig()):
        if cfg.termination.startswith("residual") and not workload.residual_capable:
            raise ValueError(
                f"termination {cfg.termination!r} needs a residual-reporting "
                f"workload (got {type(workload).__name__}); use 'eos_maxlen'"
            )
        self.workload = workload
        self.cfg = cfg
        self.slots = workload.slots
        self.dp = cfg.dp  # live replica extent (resize() changes it)
        # a dp-sharded workload (fixed-point pools) must agree with the
        # engine's extent — align it, as resize() does, so a workload that
        # served at another extent can be re-engined at any dp
        mig = getattr(workload, "migrate_dp", None)
        if mig is not None and getattr(workload, "dp", cfg.dp) != cfg.dp:
            mig(cfg.dp)
        # canonicalize the workload's device state: a fresh __init__ hands
        # the first dispatch mesh-committed leaves while a reset() hands it
        # uncommitted ones, and jit propagates that difference through every
        # downstream signature — forking the executable cache per history
        workload.params = self._commit(workload.params)
        workload.wstate = self._commit(workload.wstate)
        self.scheduler = get_scheduler(cfg.scheduler)
        self.term = get_termination(cfg.termination)
        self._build_fused()
        self.tstate = self._commit(self.term.init(self.tcfg, self.slots))
        self._ctrl = None  # device control block (pushed when host-dirty)
        self._ctrl_dirty = True

        self.tick = 0
        self.queue: List[Request] = []
        self.pending: List[Request] = []  # submitted, not yet arrived
        self.slot_req: List[Optional[Request]] = [None] * self.slots
        self.results: Dict[int, RequestResult] = {}
        self.resizes: List[ResizeEvent] = []
        # per-slot host mirrors of the device control block
        self._active = np.zeros((self.slots,), bool)
        self._admit_tick = np.zeros((self.slots,), np.int32)
        self._new_tokens = np.zeros((self.slots,), np.int32)
        self._max_new = np.ones((self.slots,), np.int32)
        self._eos = np.full((self.slots,), -1, np.int32)
        self._eps = np.full((self.slots,), cfg.eps, np.float32)
        self._t_queue = np.zeros((self.slots,), np.float64)
        self._t_first = np.zeros((self.slots,), np.float64)
        self._quotas = dict(cfg.quotas or {})
        # metrics accumulators
        self._occupancy_ticks = 0
        self._occupancy_sum = 0.0
        self._forced_at_capacity = 0
        self._retried = 0
        self._replica_ticks = 0  # sum of dp over every clock tick passed
        self._t_start: Optional[float] = None
        self._t_last = 0.0

    def _build_fused(self):
        """(Re)build the fused per-tick dispatch at the current replica
        extent ``self.dp`` — called at construction and by :meth:`resize`.

        One jitted dispatch per tick: pool step + signal assembly +
        termination tick + budget force-retire + slot deactivation, all
        fused — the engine's host loop only syncs the tiny retire/token
        vectors, which is what keeps continuous batching ahead of the
        static baseline at small per-step costs.
        """
        cfg, workload = self.cfg, self.workload
        self.tcfg = TerminationConfig(
            dp=self.dp, eps=cfg.eps, window=cfg.window
        )
        certifying = cfg.termination.startswith("residual")
        dp, slots = self.dp, self.slots
        term, tcfg = self.term, self.tcfg
        cap_fn = getattr(workload, "capacity_mask", None)

        # layer scopes, carried into every op's ``op_name`` metadata (a
        # profile's op view groups device time by them): ``pool.step`` the
        # workload's step, ``termination`` the protocol tick, and inside it
        # ``termination.agree`` the MRD stage advance
        def _fused(params, wstate, tstate, ctrl, tick):
            with jax.named_scope("pool.step"):
                wstate, tokens, residual = workload.device_step(
                    params, wstate, ctrl["active"], tick
                )
            new_tokens = jnp.where(
                ctrl["active"], ctrl["new_tokens"] + 1, ctrl["new_tokens"]
            )
            if residual is None:
                residual = jnp.zeros((dp, slots), jnp.float32)
            sig = make_signals(
                tokens=tokens, new_tokens=new_tokens, eos=ctrl["eos"],
                max_new=ctrl["max_new"], eps=ctrl["eps"],
                active=ctrl["active"], admit_tick=ctrl["admit_tick"],
                tick=tick, residual=residual,
            )
            with jax.named_scope("termination"):
                tstate, retire = term.tick(tstate, sig, tcfg)
            if certifying:
                # iteration budget exhausted before the protocol certified
                forced = ctrl["active"] & (new_tokens >= ctrl["max_new"]) & ~retire
            else:
                forced = jnp.zeros_like(retire)
            if cap_fn is not None:
                # slot frozen at cache capacity but not naturally done: it
                # can produce no further useful tokens, so force-retire NOW
                # (previously such slots spun silently until their budget)
                # — surfaced separately as `forced_at_capacity`
                nat = (tokens == ctrl["eos"]) | (new_tokens >= ctrl["max_new"])
                at_cap = (
                    ctrl["active"] & cap_fn(wstate) & ~nat & ~retire & ~forced
                )
            else:
                at_cap = jnp.zeros_like(retire)
            forced = forced | at_cap
            ctrl = {
                **ctrl,
                "active": ctrl["active"] & ~(retire | forced),
                "new_tokens": new_tokens,
            }
            return wstate, tstate, ctrl, retire, forced, at_cap, tokens

        K = cfg.steps_per_dispatch

        def _fused_loop(params, wstate, tstate, ctrl, tick0, klim):
            """Up to ``klim <= K`` fused ticks in one dispatch, early-exiting
            after the first tick that retires a slot (the host then collects
            outputs and admits from the queue)."""

            def cond(c):
                return (c["i"] < klim) & ~c["stop"] & jnp.any(c["ctrl"]["active"])

            def body(c):
                i = c["i"]
                wstate, tstate, ctrl, retire, forced, at_cap, tokens = _fused(
                    params, c["wstate"], c["tstate"], c["ctrl"], tick0 + i
                )
                return {
                    "wstate": wstate, "tstate": tstate, "ctrl": ctrl,
                    "i": i + 1,
                    "stop": jnp.any(retire | forced),
                    "active_buf": c["active_buf"].at[i].set(c["ctrl"]["active"]),
                    "tokens_buf": c["tokens_buf"].at[i].set(tokens),
                    "retire_buf": c["retire_buf"].at[i].set(retire),
                    "forced_buf": c["forced_buf"].at[i].set(forced),
                    "cap_buf": c["cap_buf"].at[i].set(at_cap),
                }

            init = {
                "wstate": wstate, "tstate": tstate, "ctrl": ctrl,
                "i": jnp.zeros((), jnp.int32),
                "stop": jnp.zeros((), jnp.bool_),
                "active_buf": jnp.zeros((K, slots), jnp.bool_),
                "tokens_buf": jnp.zeros((K, slots), jnp.int32),
                "retire_buf": jnp.zeros((K, slots), jnp.bool_),
                "forced_buf": jnp.zeros((K, slots), jnp.bool_),
                "cap_buf": jnp.zeros((K, slots), jnp.bool_),
            }
            return jax.lax.while_loop(cond, body, init)

        # compile once per (workload, termination config): engines over the
        # same workload (bench re-runs, resets, revisited elastic extents)
        # reuse the compiled tick — the key includes dp via tcfg, so each
        # extent compiles exactly once per workload
        cache = getattr(workload, "_fused_cache", None)
        if cache is None:
            cache = workload._fused_cache = {}
        key = (cfg.termination, self.tcfg, K)
        if key not in cache:
            cache[key] = jax.jit(_fused_loop)
        self._jfused = cache[key]

    # -- request intake -----------------------------------------------------

    def submit(self, req: Request) -> None:
        """Queue ``req`` (it becomes schedulable at ``req.arrival``)."""
        if req.arrival <= self.tick:
            req.arrival = self.tick
            req._t_submit = time.perf_counter()
            self.queue.append(req)
        else:
            self.pending.append(req)

    @property
    def active(self) -> np.ndarray:
        return self._active

    @property
    def usable_slots(self) -> int:
        """Slots currently funded by the replica extent (capacity model).

        With ``cfg.slots_per_replica`` set, a shrink stops *admissions*
        into the defunded tail slots — in-flight requests there drain
        naturally (nothing is preempted), then the slots idle until a
        grow refunds them.
        """
        spr = self.cfg.slots_per_replica
        return self.slots if not spr else min(self.slots, self.dp * spr)

    def _free_slots(self) -> List[int]:
        return [
            s for s in range(self.usable_slots) if self.slot_req[s] is None
        ]

    def load_snapshot(self) -> LoadSnapshot:
        """Deterministic tick-domain load picture: queue depth, TTFT-SLA
        pressure (near = past half the deadline while still queued), and
        free capacity under the ``slots_per_replica`` model.

        This is the *single* load surface: the autoscaler
        (``ElasticServeController._load``) reads it for resize decisions,
        and — when telemetry is on — the same numbers land as gauges, so
        the trace shows exactly the pressure the policy acted on.
        """
        tick = self.tick
        near = overdue = 0
        for r in self.queue:
            if r.sla is None:
                continue
            waited = tick - r.arrival
            if waited > r.sla:
                overdue += 1
            elif 2 * waited >= r.sla:
                near += 1
        snap = LoadSnapshot(
            tick=tick,
            queue_depth=len(self.queue),
            sla_near=near,
            sla_overdue=overdue,
            free_slots=len(self._free_slots()),
            usable_slots=self.usable_slots,
            dp=self.dp,
        )
        if obs.enabled():
            obs.gauge("serve.queue_depth").set(snap.queue_depth)
            obs.gauge("serve.sla_near").set(snap.sla_near)
            obs.gauge("serve.sla_overdue").set(snap.sla_overdue)
            obs.gauge("serve.free_slots").set(snap.free_slots)
            obs.gauge("serve.usable_slots").set(snap.usable_slots)
            obs.gauge("serve.dp").set(snap.dp)
        return snap

    def _commit(self, tree):
        """Pin replicated control/termination state to the workload's mesh.

        Host-pushed (uncommitted) arrays and jit outputs (committed) hash to
        different jit cache entries; committing both sides keeps the fused
        tick at exactly one compilation."""
        mesh = getattr(self.workload, "mesh", None)
        if mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(mesh, PartitionSpec())
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    # -- elastic resize (DESIGN.md S15) --------------------------------------

    def resize(self, new_dp: int, keep, *, reason: str = ""):
        """Change the termination-agreement replica extent under live
        traffic — no request is lost, no slot re-prefills.

        ``keep[i]`` is the old replica rank now at new rank ``i`` (None =
        a joiner).  On **shrink**, survivors re-agree in-flight slot state
        through the protocol ``migrate`` hooks: certified latches and
        per-replica interval windows survive, the staged MRD reduction
        restarts at the new (typically non-power-of-two) extent, and the
        re-latched cycle guard keeps every pre-resize admission retirable.
        On **grow**, the joiner receives params, KV/state cache, pool
        control state, and (paged pools) block tables + allocator
        refcounts/prefix registry through the bit-exact
        :func:`repro.distributed.serve.mrd_broadcast_stacked` path at the
        new extent.  Returns the recorded :class:`ResizeEvent` (or None
        for a no-op resize).
        """
        keep = tuple(keep)
        if new_dp < 1 or len(keep) != new_dp:
            raise ValueError(f"keep map {keep} does not cover dp={new_dp}")
        old_dp = self.dp
        for k in keep:
            if k is not None and not 0 <= k < old_dp:
                raise ValueError(f"keep entry {k} outside old extent {old_dp}")
        if new_dp == old_dp and keep == tuple(range(old_dp)):
            return None
        kind = "grow" if any(k is None for k in keep) else "shrink"

        with obs.span(
            "serve.resize",
            kind=kind,
            old_dp=old_dp,
            new_dp=new_dp,
            tick=self.tick,
            reason=reason,
        ):
            mig = getattr(self.workload, "migrate_dp", None)
            if mig is not None:
                mig(new_dp)
            old_tstate = self.tstate
            self.dp = new_dp
            self._build_fused()  # new tcfg -> new jit cache entry per extent
            with obs.span("serve.resize.migrate", kind=kind):
                self.tstate = self._commit(
                    self.term.migrate(old_tstate, keep, self.tcfg, self.slots)
                )
            if kind == "grow":
                with obs.span("serve.resize.broadcast", new_dp=new_dp):
                    self._broadcast_to_joiners()
        ev = ResizeEvent(
            kind=kind, step=self.tick, old_dp=old_dp, new_dp=new_dp,
            keep=keep, device_ids=(), reason=reason,
        )
        self.resizes.append(ev)
        return ev

    def _broadcast_to_joiners(self):
        """Route the full serving state through the MRD sum-broadcast at
        the new extent and install the *joiner's* copy — the protocol-level
        transfer a joining replica performs instead of a cold start.  The
        broadcast is bit-exact (non-source ranks contribute true zeros), so
        survivors' state is unchanged and the joiner decodes bit-identical
        tokens from its first tick; every leaf's committed sharding is
        restored so the fused tick stays at one compilation per extent.
        """
        from repro.distributed import serve as dserve

        tree = {
            "params": self.workload.params,
            "wstate": self.workload.wstate,
            "tstate": self.tstate,
        }
        if self._ctrl is not None and not self._ctrl_dirty:
            tree["ctrl"] = self._ctrl
        exp = getattr(self.workload, "export_state", None)
        if exp is not None:
            tree["host"] = exp()
        leaves, treedef = jax.tree.flatten(tree)
        shardings = [
            leaf.sharding if isinstance(leaf, jax.Array) else None
            for leaf in leaves
        ]
        out = dserve.mrd_broadcast_stacked(leaves, self.dp, src=0)
        out = [
            jax.device_put(o, s) if s is not None else np.asarray(o)
            for o, s in zip(out, shardings)
        ]
        tree = jax.tree.unflatten(treedef, out)
        self.workload.params = tree["params"]
        self.workload.wstate = tree["wstate"]
        self.tstate = tree["tstate"]
        if "ctrl" in tree:
            self._ctrl = tree["ctrl"]
        if "host" in tree:
            self.workload.import_state(tree["host"])

    def _abort_inflight(self):
        """A crashed fused dispatch must not leak cache blocks or strand
        requests: every in-flight slot's blocks are rolled back to the
        allocator and its request returns to the queue for a clean
        re-admission (the tick never happened as far as the request is
        concerned)."""
        rel = getattr(self.workload, "release", None)
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            if rel is not None:
                rel(slot)
            self.slot_req[slot] = None
            self._active[slot] = False
            self.queue.append(req)
        self._ctrl_dirty = True

    # -- one tick -----------------------------------------------------------

    def _after_admit(self, req, slot: int, now: int, t0: float) -> None:
        """Slot bookkeeping for a just-admitted request."""
        self.slot_req[slot] = req
        self._active[slot] = True
        self._admit_tick[slot] = now
        # llm: the prefill's argmax token; fixedpoint: no iteration yet
        self._new_tokens[slot] = self.workload.prefill_tokens
        self._max_new[slot] = self.workload.clamp_max_new(req)
        self._eos[slot] = req.eos
        self._eps[slot] = self.cfg.eps if req.eps is None else req.eps
        self._t_queue[slot] = getattr(req, "_t_submit", t0)
        self._t_first[slot] = time.perf_counter()
        self._ctrl_dirty = True

    def step(self) -> np.ndarray:
        """Advance one tick; returns the retired-slot mask ``[S]``."""
        if self._t_start is None:
            self._t_start = time.perf_counter()
        now = self.tick
        # release arrivals into the schedulable queue (TTFT clock starts
        # when a request becomes visible, not when the caller built it)
        still = []
        for r in self.pending:
            if r.arrival <= now:
                r._t_submit = time.perf_counter()
                self.queue.append(r)
            else:
                still.append(r)
        self.pending = still

        # 1. admit: walk the scheduler's order, filling free slots with the
        # first *eligible* requests — a request blocked by its tenant quota
        # or the cache-block budget is passed over (it stays queued) and
        # the slot goes to the next request instead of idling a tick
        free = self._free_slots()
        if self.cfg.max_admit_per_tick:
            free = free[: self.cfg.max_admit_per_tick]
        gate = getattr(self.workload, "can_admit", None)
        inflight: Dict[str, int] = {}
        if self._quotas:
            for r in self.slot_req:
                if r is not None:
                    inflight[r.tenant] = inflight.get(r.tenant, 0) + 1
        ordered = (
            self.scheduler.order(list(self.queue), now)
            if self.queue and free else []
        )
        n_admitted = 0
        with obs.span("serve.admit", tick=now, queue_depth=len(self.queue)) as sp:
            for req in ordered:
                if not free:
                    break
                quota = self._quotas.get(req.tenant, 0)
                if quota and inflight.get(req.tenant, 0) >= quota:
                    continue  # tenant at its admission quota: req stays queued
                if gate is not None and not gate(req):
                    continue  # out of cache blocks: req waits in the queue
                slot = free.pop(0)
                self.queue.remove(req)
                if self._quotas:
                    inflight[req.tenant] = inflight.get(req.tenant, 0) + 1
                t0 = req._t_admit = time.perf_counter()
                with obs.span("serve.admit.request") as rsp:
                    if rsp is not None:
                        rsp.update(slot=slot, request=req.id, queued_ms=1e3 * (
                            t0 - getattr(req, "_t_submit", t0)))
                        if req.prompt is not None:
                            rsp["prompt_len"] = len(req.prompt)
                    self.workload.admit(req, slot, now)
                    self._after_admit(req, slot, now, t0)
                n_admitted += 1
            if sp is not None:
                sp["n_admitted"] = n_admitted

        if not self._active.any():
            # nothing in flight: fast-forward the virtual clock to the next
            # arrival instead of burning empty device ticks
            self.tick = (
                min(r.arrival for r in self.pending)
                if self.pending else now + 1
            )
            # provisioned-but-idle replicas still cost replica-ticks —
            # that is exactly the waste the autoscaler exists to shed
            self._replica_ticks += (self.tick - now) * self.dp
            self._t_last = time.perf_counter()
            return np.zeros((self.slots,), bool)

        if self._ctrl_dirty:
            with obs.span("serve.ctrl"):
                ctrl = {
                    "active": jnp.asarray(self._active),
                    "new_tokens": jnp.asarray(self._new_tokens),
                    "admit_tick": jnp.asarray(self._admit_tick),
                    "eos": jnp.asarray(self._eos),
                    "max_new": jnp.asarray(self._max_new),
                    "eps": jnp.asarray(self._eps),
                }
                self._ctrl = self._commit(ctrl)
            self._ctrl_dirty = False

        # 2-3. pool steps + termination ticks, one fused dispatch running up
        # to `klim` ticks (early exit on the first retiring tick); capped at
        # the next pending arrival so scheduling never waits on the device
        klim = self.cfg.steps_per_dispatch
        if self.pending:
            nxt = min(r.arrival for r in self.pending)
            klim = max(1, min(klim, nxt - now))
        if self.cfg.max_admit_per_tick and self.queue and self._free_slots():
            klim = 1  # rate-limited admissions resume next tick
        with obs.span("serve.tick", tick=now, klim=klim, dp=self.dp) as sp:
            try:
                final = self._jfused(
                    self.workload.params, self.workload.wstate, self.tstate,
                    self._ctrl, jnp.int32(now), jnp.int32(klim),
                )
            except Exception:
                self._abort_inflight()
                raise
            self.workload.wstate = final["wstate"]
            self.tstate = final["tstate"]
            self._ctrl = final["ctrl"]
            with obs.span("serve.wait"):  # the host waits on the device
                n_ticks = int(final["i"])
            # convert whole buffers, slice on host: device-side slicing at a
            # data-dependent length would compile one kernel per distinct
            # length
            active_buf = np.asarray(final["active_buf"])[:n_ticks]
            tokens_buf = np.asarray(final["tokens_buf"])[:n_ticks]

            with obs.span("serve.collect"):
                for k in range(n_ticks):
                    act = active_buf[k]
                    self._new_tokens[act] += 1
                    self.workload.collect_tick(tokens_buf[k], act)
                    self._occupancy_sum += float(act.sum()) / self.slots
                    self._occupancy_ticks += 1
            if sp is not None:
                sp["n_ticks"] = n_ticks

        # 4. retire: by construction only the last executed tick can retire
        # (the device loop exits right after it)
        last = n_ticks - 1
        with obs.span("serve.flags"):  # the last tick's flags, off the device
            retire = np.asarray(final["retire_buf"])[last]
            forced = np.asarray(final["forced_buf"])[last]
            at_cap = np.asarray(final["cap_buf"])[last]
        out_mask = retire | forced
        if out_mask.any():
            slots = np.nonzero(out_mask)[0]
            with obs.span("serve.retire") as sp:
                if sp is not None:
                    sp.update(
                        tick=now + last, n=len(slots),
                        forced=int(forced[slots].sum()), slots=slots.tolist(),
                        requests=[getattr(self.slot_req[s], "id", None)
                                  for s in slots],
                    )
                self._active[out_mask] = False
                certified = np.asarray(self.tstate["certified"])
                t_done = time.perf_counter()
                for slot in slots:
                    self._collect(int(slot), now + last, certified,
                                  bool(forced[slot]), t_done,
                                  at_capacity=bool(at_cap[slot]))
        self.tick = now + n_ticks
        self._replica_ticks += n_ticks * self.dp
        self._t_last = time.perf_counter()
        if obs.enabled():
            self.load_snapshot()  # records the load gauges for this step
        return out_mask

    def _collect(self, slot, now, certified, was_forced, t_done,
                 at_capacity=False):
        req = self.slot_req[slot]
        if at_capacity:
            self._forced_at_capacity += 1
            if getattr(req, "_retries", 0) < self.cfg.max_retries:
                # bounded requeue: the request frozen at capacity gets a
                # fresh admission (and a fresh block reservation) instead
                # of silently retiring converged=False
                req._retries = getattr(req, "_retries", 0) + 1
                self._retried += 1
                self.slot_req[slot] = None
                rel = getattr(self.workload, "release", None)
                if rel is not None:
                    rel(slot)
                req.arrival = self.tick
                self.queue.append(req)
                return
        out = self.workload.output(slot)
        n_tok = int(self._new_tokens[slot])
        if req.prompt is not None:  # llm: trim to EOS / budget
            toks = out[: min(n_tok, int(self._max_new[slot]))]
            hits = np.nonzero(toks == req.eos)[0]
            if req.eos >= 0 and hits.size:
                toks = toks[: hits[0] + 1]
            out = toks
            n_tok = int(out.shape[0])
        ttft = self._t_first[slot] - self._t_queue[slot]
        # a single-token completion has no inter-token interval: reporting
        # 0.0 s here dragged TPOT percentiles down in mixed-length traffic,
        # so it is NaN and summary() excludes it from the percentiles
        tpot = (
            (t_done - self._t_first[slot]) / (n_tok - 1)
            if n_tok > 1 else float("nan")
        )
        admit_tick = int(self._admit_tick[slot])
        # TTFT SLA is tick-domain (deterministic): first token no later
        # than `sla` ticks after the request became schedulable
        sla_met = (
            None if req.sla is None
            else bool(admit_tick - req.arrival <= req.sla)
        )
        # the protocol's per-slot certified latch is only written on
        # protocol retirement; a budget-forced request must not inherit the
        # value its slot's *previous* occupant certified at
        cert = RES_INIT if was_forced else float(certified[slot])
        self.results[req.id] = RequestResult(
            id=req.id, output=out, arrival=req.arrival,
            admit_tick=admit_tick, retire_tick=now,
            n_tokens=n_tok, certified=cert,
            converged=not was_forced, ttft_s=ttft, tpot_s=tpot,
            queued_s=req._t_admit - self._t_queue[slot],
            retries=getattr(req, "_retries", 0),
            tenant=req.tenant, sla=req.sla, sla_met=sla_met,
        )
        self.slot_req[slot] = None
        rel = getattr(self.workload, "release", None)
        if rel is not None:
            rel(slot)  # paged pools return the slot's blocks to the allocator

    # -- drive to completion ------------------------------------------------

    def run(self, requests=None, *, max_ticks: Optional[int] = None):
        """Submit ``requests`` (scheduled by their ``arrival`` ticks) and
        step until everything submitted has retired.  Returns ``results``."""
        for r in requests or []:
            self.submit(r)
        budget = max_ticks or self.cfg.max_ticks
        steps = 0
        while self.queue or self.pending or any(self.slot_req):
            if steps >= budget:
                raise RuntimeError(
                    f"serve loop did not drain within {budget} engine steps "
                    f"({len(self.queue) + len(self.pending)} queued, "
                    f"{sum(r is not None for r in self.slot_req)} in flight)"
                )
            self.step()
            steps += 1
        return self.results

    # -- metrics ------------------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        res = list(self.results.values())
        wall = (self._t_last - self._t_start) if self._t_start else 0.0
        return {
            "completed": len(res),
            "ticks": self.tick,
            "wall_s": wall,
            "tokens_out": int(sum(r.n_tokens for r in res)),
            "throughput_tok_s": (
                sum(r.n_tokens for r in res) / wall if wall > 0 else 0.0
            ),
            # percentiles are NaN — never a fake 0 ms — when no request
            # retired (or, for TPOT, when every completion was single-token
            # and carries no inter-token interval); bench `--check` gates
            # treat a NaN percentile as a hard failure, not a pass
            **_latency_percentiles(res),
            "occupancy": (
                self._occupancy_sum / self._occupancy_ticks
                if self._occupancy_ticks else 0.0
            ),
            **_sla_fields(res, self.tick, wall),
            "replica_ticks": self._replica_ticks,
            "tenants": _tenant_summaries(res),
            "converged": int(sum(r.converged for r in res)),
            "forced_at_capacity": self._forced_at_capacity,
            "retried": self._retried,
            "resizes": len(self.resizes),
            # pipeline health of the telemetry plane itself — span counts
            # and ring-buffer overflow are surfaced here so a saturated
            # tracer is observable, never silent
            "telemetry": obs.summary(),
        }


def _pct_ms(seconds: np.ndarray, q: float) -> float:
    """NaN-safe percentile in milliseconds (NaN when nothing to rank)."""
    finite = seconds[np.isfinite(seconds)]
    return float(np.percentile(finite, q) * 1e3) if finite.size else float("nan")


def _latency_percentiles(res) -> Dict[str, float]:
    ttft = np.asarray([r.ttft_s for r in res], np.float64)
    tpot = np.asarray([r.tpot_s for r in res], np.float64)
    queue = np.asarray([r.queued_s for r in res], np.float64)
    out = {}
    for q in (50, 95, 99):
        out[f"ttft_p{q}_ms"] = _pct_ms(ttft, q)
        out[f"tpot_p{q}_ms"] = _pct_ms(tpot, q)
        out[f"queue_p{q}_ms"] = _pct_ms(queue, q)
    return out


def _sla_fields(res, ticks: int, wall: float) -> Dict[str, Any]:
    """Goodput under SLA.  ``sla_met`` counts requests whose tick-domain
    TTFT met their deadline (over the ``sla_total`` that carry one);
    ``goodput_ok`` adds completed no-SLA (batch) requests, and the rates
    divide by elapsed ticks (deterministic — what the CI gates compare)
    and wall seconds."""
    sla_total = sum(1 for r in res if r.sla is not None)
    sla_met = sum(1 for r in res if r.sla_met)
    goodput_ok = sla_met + (len(res) - sla_total)
    return {
        "sla_total": sla_total,
        "sla_met": sla_met,
        "goodput_ok": goodput_ok,
        "goodput_per_ktick": (
            goodput_ok / ticks * 1000.0 if ticks > 0 else 0.0
        ),
        "goodput_req_s": goodput_ok / wall if wall > 0 else 0.0,
    }


def _tenant_summaries(res) -> Dict[str, Dict[str, Any]]:
    """Per-tenant breakdown (empty when the traffic is untenanted)."""
    by: Dict[str, list] = {}
    for r in res:
        by.setdefault(r.tenant, []).append(r)
    if set(by) <= {""}:
        return {}
    out = {}
    for name in sorted(by):
        rs = by[name]
        ttft_ticks = np.asarray(
            [r.admit_tick - r.arrival for r in rs], np.float64
        )
        sla_total = sum(1 for r in rs if r.sla is not None)
        sla_met = sum(1 for r in rs if r.sla_met)
        out[name] = {
            "completed": len(rs),
            "tokens_out": int(sum(r.n_tokens for r in rs)),
            "sla_total": sla_total,
            "sla_met": sla_met,
            "goodput_ok": sla_met + (len(rs) - sla_total),
            "ttft_p99_ticks": (
                float(np.percentile(ttft_ticks, 99)) if rs else float("nan")
            ),
            **_latency_percentiles(rs),
        }
    return out
