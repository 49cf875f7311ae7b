"""Unified telemetry subsystem (repro/obs/, DESIGN.md S18): instruments,
ring-buffer overflow accounting, background drain, tracer + Chrome-trace
export, sinks, and the instrumented subsystem integration points."""

import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import MetricsRegistry, Tracer, get_sink, parse_spec


@pytest.fixture(autouse=True)
def _fresh_global():
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


# -- metrics -----------------------------------------------------------------


def test_counter_accumulates_and_labels_key_separately():
    reg = MetricsRegistry()
    reg.counter("msgs", schedule="mrd").add(3)
    reg.counter("msgs", schedule="mrd").add(4)
    reg.counter("msgs", schedule="ring").add(10)
    snap = reg.snapshot()
    assert snap["counters"]["msgs[schedule=mrd]"] == 7.0
    assert snap["counters"]["msgs[schedule=ring]"] == 10.0


def test_gauge_last_value_wins():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(3)
    g.set(9)
    assert reg.snapshot()["gauges"]["depth"] == 9.0


def test_gauge_accepts_device_array_materialized_at_drain():
    reg = MetricsRegistry()
    reg.gauge("loss").set(jnp.float32(2.5))  # stored by reference
    assert reg.snapshot()["gauges"]["loss"] == 2.5


def test_histogram_stats_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    st = reg.snapshot()["histograms"]["lat"]
    assert st["count"] == 5 and st["min"] == 1.0 and st["max"] == 100.0
    assert st["sum"] == 110.0 and st["mean"] == 22.0
    assert st["p50"] == 3.0


def test_ring_overflow_drops_and_counts_never_blocks():
    reg = MetricsRegistry(capacity=8)
    c = reg.counter("x")
    for _ in range(20):
        c.add(1)
    assert reg.dropped == 12
    assert reg.summary()["dropped"] == 12
    reg.flush()
    # the 8 ring slots drained; overflow was dropped, not queued
    assert reg.snapshot()["counters"]["x"] == 8.0


def test_drain_frees_ring_capacity():
    reg = MetricsRegistry(capacity=8)
    c = reg.counter("x")
    for _ in range(8):
        c.add(1)
    reg.flush()
    for _ in range(8):
        c.add(1)
    reg.flush()
    assert reg.dropped == 0
    assert reg.snapshot()["counters"]["x"] == 16.0


def test_background_writer_drains_without_explicit_flush():
    reg = MetricsRegistry(capacity=64, interval=0.01)
    reg.start()
    try:
        reg.counter("bg").add(5)
        done = threading.Event()
        for _ in range(200):
            if reg.summary()["pending"] == 0 and reg.summary()["drained"] >= 1:
                done.set()
                break
            threading.Event().wait(0.01)
        assert done.is_set(), "writer thread never drained the ring"
    finally:
        reg.stop()
    assert reg.snapshot()["counters"]["bg"] == 5.0


def test_sink_receives_drained_batches():
    class Capture:
        name = "capture"

        def __init__(self):
            self.rows = []

        def write_metrics(self, batch):
            self.rows.extend(batch)

        def close(self, tracer=None):
            pass

    reg = MetricsRegistry()
    cap = Capture()
    reg._sink = cap
    reg.counter("a", k="v").add(2)
    reg.flush()
    assert len(cap.rows) == 1
    ts, kind, name, value, labels = cap.rows[0]
    assert kind == "counter" and name == "a" and value == 2.0
    assert dict(labels) == {"k": "v"}


# -- tracing -----------------------------------------------------------------


def test_span_records_duration_and_args():
    tr = Tracer()
    with tr.span("work", n=3) as sp:
        sp["m"] = 7  # attached mid-span, lands in the exported args
    evs = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert len(evs) == 1
    assert evs[0]["name"] == "work"
    assert evs[0]["args"] == {"n": 3, "m": 7}
    assert evs[0]["dur"] >= 0


def test_instant_and_span_counts():
    tr = Tracer()
    with tr.span("a"):
        tr.instant("mark", tick=1)
    s = tr.summary()
    assert s["spans"] == 1 and s["instants"] == 1 and s["dropped"] == 0
    assert tr.counts() == {"a": 1, "mark": 1}


def test_tracer_overflow_counts_dropped():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert tr.summary()["recorded"] == 4
    assert tr.summary()["dropped"] == 6


def test_chrome_trace_is_perfetto_shaped():
    tr = Tracer()
    with tr.span("outer"):
        tr.instant("inner")
    doc = tr.chrome_trace(process_name="test-proc")
    json.dumps(doc)  # serializable
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {m["name"] for m in metas}
    xs = [e for e in evs if e["ph"] == "X"]
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(xs) == 1 and len(inst) == 1
    for e in xs + inst:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
    assert inst[0]["s"] == "t"
    # relative microsecond timestamps: instant falls inside the span
    assert xs[0]["ts"] <= inst[0]["ts"] <= xs[0]["ts"] + xs[0]["dur"]


def test_span_exception_still_records():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert tr.summary()["spans"] == 1


def test_writer_thread_gets_own_lane(tmp_path):
    tr = Tracer()
    with tr.span("main-side"):
        pass
    t = threading.Thread(target=lambda: tr.instant("thread-side"))
    t.start()
    t.join()
    evs = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] in "Xi"]
    tids = {e["name"]: e["tid"] for e in evs}
    assert tids["main-side"] != tids["thread-side"]


# -- sinks -------------------------------------------------------------------


def test_parse_spec():
    assert parse_spec("null") == ("null", None)
    assert parse_spec("jsonl:out.jsonl") == ("jsonl", "out.jsonl")
    assert parse_spec("chrome_trace:/tmp/t.json") == (
        "chrome_trace",
        "/tmp/t.json",
    )
    with pytest.raises(ValueError, match="unknown telemetry sink"):
        parse_spec("bogus")


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = get_sink(f"jsonl:{path}")
    sink.write_metrics([(123, "counter", "a", 2.0, (("k", "v"),))])
    sink.close()
    rows = [json.loads(l) for l in open(path)]
    assert rows[0] == {
        "ts_ns": 123,
        "kind": "counter",
        "name": "a",
        "value": 2.0,
        "labels": {"k": "v"},
    }


def test_csv_sink_round_trip(tmp_path):
    path = str(tmp_path / "t.csv")
    sink = get_sink(f"csv:{path}")
    sink.write_metrics([(123, "gauge", "g", 1.5, ())])
    sink.close()
    lines = open(path).read().strip().splitlines()
    assert lines[0] == "ts_ns,kind,name,value,labels"
    assert lines[1].startswith("123,gauge,g,1.5")


def test_chrome_trace_sink_writes_trace_at_close(tmp_path):
    path = str(tmp_path / "trace.json")
    sink = get_sink(f"chrome_trace:{path}")
    tr = Tracer()
    with tr.span("s"):
        pass
    sink.close(tr)
    doc = json.load(open(path))
    assert any(e.get("name") == "s" for e in doc["traceEvents"])


# -- global facade -----------------------------------------------------------


def test_disabled_by_default_records_nothing():
    assert not obs.enabled()
    with obs.span("never") as sp:
        assert sp is None
    obs.instant("never")
    assert obs.telemetry().tracer.summary()["recorded"] == 0


def test_configure_shutdown_round_trip(tmp_path):
    path = str(tmp_path / "out.json")
    obs.configure(f"chrome_trace:{path}", background=False)
    with obs.span("run", p=5):
        obs.instant("tick")
    obs.counter("n").add(1)
    summary = obs.shutdown()
    assert summary["spans"] == 1 and summary["instants"] == 1
    assert summary["sink"] == "chrome_trace"
    assert not obs.enabled()
    names = [e.get("name") for e in json.load(open(path))["traceEvents"]]
    assert "run" in names and "tick" in names


# -- instrumented subsystems -------------------------------------------------


def test_collective_plan_emits_paper_message_counts():
    from repro.collectives.plans import allreduce_plan
    from repro.core import topology

    p = 5
    obs.configure("null", background=False)
    plan = allreduce_plan(schedule="mrd", executor="sim", p=p)
    plan.run(jnp.ones((p, 8), jnp.float32))
    snap = obs.snapshot()
    assert snap["counters"]["coll.messages[schedule=mrd]"] == float(
        topology.paper_message_count(p)
    )
    _p0, _mu0, extra = topology.pivot(p)
    assert snap["counters"]["coll.extra_msgs[schedule=mrd]"] == float(2 * extra)
    stage_events = obs.telemetry().tracer.counts("coll.stage")
    assert stage_events["coll.stage"] == topology.paper_step_count(p)


def test_collective_run_buffers_scales_messages_by_bucket_count():
    from repro.collectives.plans import allreduce_plan
    from repro.core import topology

    p, n_bufs = 3, 4
    obs.configure("null", background=False)
    plan = allreduce_plan(schedule="mrd", executor="sim", p=p)
    plan.run_buffers([jnp.ones((p, 8), jnp.float32)] * n_bufs)
    snap = obs.snapshot()
    assert snap["counters"]["coll.messages[schedule=mrd]"] == float(
        n_bufs * topology.paper_message_count(p)
    )


def test_async_run_emits_certify_instant():
    from repro.asynchrony.engine import AsyncConfig, run
    from repro.asynchrony.solvers import make_solver

    obs.configure("null", background=False)
    fp = make_solver("poisson1d", n=64, shift=0.5, seed=0)
    res = run(fp, AsyncConfig(p=4, detection="exact", eps=1e-5, max_ticks=50000))
    assert res.detected
    counts = obs.telemetry().tracer.counts()
    assert counts["async.run"] == 1
    assert counts["protocol.certify"] == 1
    snap = obs.snapshot()
    assert snap["counters"]["async.messages_coll[protocol=exact]"] == float(
        res.messages_coll
    )


def test_serve_engine_summary_has_telemetry_subdict():
    from repro.serving import Request, ServeConfig, ServeEngine, make_workload

    obs.configure("null", background=False)
    wl = make_workload("fixedpoint_solve", solver="d_iteration", n=16, slots=2)
    eng = ServeEngine(wl, ServeConfig(termination="residual_interval"))
    rng = np.random.default_rng(0)
    v = rng.random(16).astype(np.float32)
    eng.run([Request(id=0, arrival=0, payload=v / v.sum(), max_new=400)])
    s = eng.summary()
    assert s["completed"] == 1
    tele = s["telemetry"]
    assert tele["enabled"] is True
    assert tele["spans"] > 0  # admit/tick spans recorded
    assert tele["events_dropped"] == 0
    counts = obs.telemetry().tracer.counts("serve.")
    assert counts["serve.admit"] >= 1
    assert counts["serve.tick"] >= 1
    assert counts["serve.retire"] == 1


def _host_events(tmp_path, fn):
    """Run ``fn`` under a jax.profiler session; the host planes' events."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    pd = ProfileData.from_file(path)
    return [(e.name, e.duration_ns, dict(e.stats))
            for pl in pd.planes if pl.name.startswith("/host:")
            for line in pl.lines for e in line.events]


def test_enabled_span_lands_on_the_profilers_host_plane(tmp_path):
    obs.configure("null", background=False)

    def body():
        with obs.span("serve.tick", tick=3) as sp:
            sp["n_ticks"] = 2  # attached at exit: still on the trace
        obs.instant("serve.mark", k=1)

    evs = {n: (d, st) for n, d, st in _host_events(tmp_path, body)}
    assert evs["serve.tick"][1] == {"tick": 3, "n_ticks": 2}
    assert evs["serve.mark"][1] == {"k": 1}
    assert obs.telemetry().tracer.counts("serve.") == {
        "serve.tick": 1, "serve.mark": 1}


def test_disabled_span_records_nothing_and_opens_no_annotation(tmp_path):
    from repro.obs.tracing import _NULL_SPAN

    assert obs.span("never.span", a=1) is _NULL_SPAN

    def body():
        with obs.span("never.span", a=1):
            obs.instant("never.instant")

    names = {n for n, _, _ in _host_events(tmp_path, body)}
    assert not names & {"never.span", "never.instant"}
    assert obs.telemetry().tracer.summary()["recorded"] == 0


def test_fresh_jit_under_telemetry_yields_one_compile_span(tmp_path):
    import jax

    x = jnp.ones((7,), jnp.float32)
    obs.configure("null", background=False)
    evs = _host_events(
        tmp_path, lambda: jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready())
    spans = [e for e in obs.telemetry().tracer.events() if e[1] == "jax.compile"]
    assert len(spans) == 1
    ph, _, ts, dur, _, args = spans[0]
    assert ph == "X" and dur > 0 and "lambda" in args["fun"]
    # on the profiler's trace: a marker at its end that carries its length
    [(d, st)] = [(d, st) for n, d, st in evs if n == "jax.compile"]
    assert d < st["dur_ns"] == dur
    obs.shutdown()  # the listener goes with the session
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    assert obs.telemetry().tracer.counts("jax.") == {"jax.compile": 1}


def test_fused_tick_has_layer_scopes():
    from repro.serving import Request, ServeConfig, ServeEngine, make_workload

    wl = make_workload("fixedpoint_solve", solver="d_iteration", n=24, slots=2, dp=3)
    eng = ServeEngine(wl, ServeConfig(termination="residual_interval", dp=3))
    eng.submit(Request(id=0, max_new=50))
    eng.step()
    text = eng._jfused.lower(
        wl.params, wl.wstate, eng.tstate, eng._ctrl, jnp.int32(0), jnp.int32(4)
    ).compile().as_text()
    body = 'op_name="jit(_fused_loop)/while/body/'
    assert body + "pool.step/" in text
    assert body + "termination/" in text
    assert body + "termination/termination.agree/" in text


def test_admit_request_span_carries_queue_wait():
    from repro.serving import Request, ServeConfig, ServeEngine, make_workload

    obs.configure("null", background=False)
    wl = make_workload("fixedpoint_solve", solver="d_iteration", n=16, slots=1)
    eng = ServeEngine(wl, ServeConfig(termination="residual_interval"))
    rng = np.random.default_rng(0)
    for i in range(2):  # one slot: the second request waits for the first
        v = rng.random(16).astype(np.float32)
        eng.submit(Request(id=i, payload=v / v.sum(), max_new=400))
    res = eng.run()
    evs = [e for e in obs.telemetry().tracer.events()
           if e[1] == "serve.admit.request"]
    queued = {e[5]["request"]: e[5]["queued_ms"] for e in evs}
    assert sorted(queued) == [0, 1]
    assert 0 <= queued[0] < queued[1]
    for rid, ms in queued.items():
        assert res[rid].queued_s * 1e3 == pytest.approx(ms)
    assert eng.summary()["queue_p99_ms"] == pytest.approx(
        np.percentile([queued[0], queued[1]], 99))
    retires = [e[5] for e in obs.telemetry().tracer.events()
               if e[1] == "serve.retire"]
    assert [r["requests"] for r in retires] == [[0], [1]]
    assert all(r["n"] == 1 and r["forced"] == 0 for r in retires)


@pytest.mark.parametrize("arch,kv_dtype,paged,want", [
    ("llama3.2-1b", "bf16", True, "one_pass"),
    ("minicpm-2b", "bf16", False, "one_pass"),
    ("zamba2-2.7b", "bf16", True, "scan"),
    ("llama4-scout-17b-a16e", "bf16", True, "scan"),
    ("gemma3-12b", "bf16", False, "scan"),
    ("llama3.2-1b", "int8", True, "scan"),
])
def test_prefill_span_names_the_admission_path(arch, kv_dtype, paged, want):
    """``paged.prefill`` (block-paged pool) and ``pool.prefill`` (contiguous
    pool) say which admission ran: one causal pass for dense K/V caches,
    the decode scan for hybrid, moe, gemma3 and int8 K/V."""
    import dataclasses

    import jax
    from jax.sharding import AxisType

    from repro.configs import registry
    from repro.models import transformer
    from repro.serving import DecodePool, PagedDecodePool

    cfg = dataclasses.replace(registry.get_smoke_config(arch),
                              kv_cache_dtype=kv_dtype)
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1],
                         axis_types=(AxisType.Auto,))
    obs.configure("null", background=False)
    if paged:
        pool = PagedDecodePool(cfg, mesh, slots=1, max_len=16, max_prompt_len=8,
                               block_size=8)
        admit = {"max_new": 4}
    else:
        pool = DecodePool(cfg, mesh, slots=1, max_len=16, max_prompt_len=8)
        admit = {}
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    pool.admit(params, np.arange(1, 6), 0, **admit)
    name = "paged.prefill" if paged else "pool.prefill"
    spans = [e[5] for e in obs.telemetry().tracer.events() if e[1] == name]
    assert spans == [{"path": want}]
    assert pool.prefill_path == want


def test_serve_engine_summary_telemetry_disabled_is_benign():
    from repro.serving import Request, ServeConfig, ServeEngine, make_workload

    wl = make_workload("fixedpoint_solve", solver="d_iteration", n=16, slots=2)
    eng = ServeEngine(wl, ServeConfig(termination="residual_interval"))
    rng = np.random.default_rng(0)
    v = rng.random(16).astype(np.float32)
    eng.run([Request(id=0, arrival=0, payload=v / v.sum(), max_new=400)])
    s = eng.summary()
    assert s["telemetry"]["enabled"] is False
    assert s["telemetry"]["spans"] == 0


def test_load_snapshot_single_source_for_policy_and_gauges():
    from repro.serving import Request, ServeConfig, ServeEngine, make_workload

    obs.configure("null", background=False)
    wl = make_workload("fixedpoint_solve", solver="d_iteration", n=16, slots=2)
    eng = ServeEngine(wl, ServeConfig(termination="residual_interval"))
    rng = np.random.default_rng(0)
    for i in range(4):  # more requests than slots: a queue forms
        v = rng.random(16).astype(np.float32)
        eng.submit(Request(id=i, arrival=0, payload=v / v.sum(), max_new=400))
    snap = eng.load_snapshot()
    assert snap.queue_depth == 4
    gauges = obs.snapshot()["gauges"]
    assert gauges["serve.queue_depth"] == float(snap.queue_depth)
    assert gauges["serve.free_slots"] == float(snap.free_slots)
    assert gauges["serve.dp"] == float(snap.dp)


def test_checkpointer_save_spans(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer

    obs.configure("null", background=False)
    ck = Checkpointer(str(tmp_path))
    state = {"w": jnp.arange(8, dtype=jnp.float32), "step": jnp.int32(1)}
    ck.save(1, state, block=True)
    counts = obs.telemetry().tracer.counts("ckpt.")
    assert counts["ckpt.save.stage"] == 1
    assert counts["ckpt.d2h_wait"] == 1
    assert counts["ckpt.write"] == 1
    # the writer-thread spans carry a different tid than the caller's
    evs = obs.telemetry().tracer.events()
    tid = {name: t for _, name, _, _, t, _ in evs}
    assert tid["ckpt.write"] != tid["ckpt.save.stage"]
