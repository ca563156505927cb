"""Telemetry plane suite: registry semantics, tracer schema, the overhead
contract, fault-stat mirroring, and instrumentation-under-differential.

What the acceptance criteria pin here:

- telemetry-OFF ingest is byte-identical to telemetry-ON ingest (patches
  AND device plane), and the disabled path adds no measurable per-call
  work (allocation-free null span, bounded relative timing);
- telemetry-ON emits valid Chrome trace-event JSONL (every line schema-
  checked) whose mirrored fault counters match ``FaultPlan.stats``
  EXACTLY under seeded chaos (same seed + call order ⇒ same counts);
- the registry survives concurrent ``ChangeQueue`` timer-thread flushes
  plus foreground hammering with no lost increments and no tracer
  corruption;
- the engine differential (delta vs scan patch paths, TpuDoc vs oracle)
  stays green with tracing enabled — instrumentation breakage surfaces
  here, in tier-1.
"""
import json
import os
import threading
import time
import tracemalloc
from timeit import repeat as timeit_repeat

import numpy as np
import pytest

from peritext_tpu.oracle import Doc
from peritext_tpu.ops import TpuUniverse
from peritext_tpu.ops.doc import TpuDoc
from peritext_tpu.runtime import ChangeLog, ChangeQueue, Publisher, faults, telemetry
from peritext_tpu.runtime.checkpoint import save_universe
from peritext_tpu.runtime.faults import FaultError, FaultPlan
from peritext_tpu.testing import patch_path_env

STATE_FIELDS = (
    "elem_ctr", "elem_act", "deleted", "chars", "bnd_def", "bnd_mask",
    "mark_ctr", "mark_act", "mark_action", "mark_type", "mark_attr",
    "length", "mark_count",
)


@pytest.fixture(autouse=True)
def _clean_planes(monkeypatch):
    """Pristine telemetry + fault planes around every test, fast backoff.

    The ambient plane (e.g. a suite-wide PERITEXT_TRACE run — the
    advertised instrumentation-breakage check) is DETACHED, not destroyed:
    its tracer/registry/enabled state are stashed and restored afterwards,
    so tests collected after this file still trace into the user's file."""
    saved = (
        telemetry.enabled,
        telemetry._tracer,
        telemetry._metrics_path,
        telemetry._registry,
        telemetry._recorder,
        telemetry._blackbox_dir,
    )
    telemetry.enabled = False
    telemetry._tracer = None
    telemetry._metrics_path = None
    telemetry._registry = telemetry.Registry()
    telemetry._recorder = None
    telemetry._blackbox_dir = None
    faults.reset()
    monkeypatch.delenv("PERITEXT_FAULTS", raising=False)
    monkeypatch.setenv("PERITEXT_LAUNCH_BACKOFF", "0.001")
    yield
    telemetry.reset()  # closes any tracer the test itself opened
    (
        telemetry.enabled,
        telemetry._tracer,
        telemetry._metrics_path,
        telemetry._registry,
        telemetry._recorder,
        telemetry._blackbox_dir,
    ) = saved
    faults.reset()


def device_plane(uni):
    return {f: np.asarray(getattr(uni.states, f)).copy() for f in STATE_FIELDS}


def assert_chrome_trace(path):
    """Schema-check every line as a Chrome trace event; returns the number
    of complete ('X') events.  Flow events ('s'/'t'/'f' — the causal-flow
    plane) must carry a flow id and the flow category."""
    with open(path) as f:
        lines = f.read().splitlines()
    assert lines, "trace file is empty"
    n_complete = 0
    for line in lines:
        event = json.loads(line)  # every line is one standalone JSON object
        assert event["ph"] in ("X", "M", "s", "t", "f"), event
        assert isinstance(event["name"], str) and event["name"]
        assert isinstance(event["pid"], int)
        assert isinstance(event["tid"], int)
        if event["ph"] == "X":
            assert isinstance(event["ts"], (int, float)) and event["ts"] >= 0
            assert isinstance(event["dur"], (int, float)) and event["dur"] >= 0
            assert event["cat"] == "peritext"
            n_complete += 1
        elif event["ph"] in ("s", "t", "f"):
            assert event["cat"] == "peritext.flow", event
            assert isinstance(event["id"], int), event
            assert isinstance(event["ts"], (int, float)) and event["ts"] >= 0
    return n_complete


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_counters_gauges_histograms():
    telemetry.enable()
    telemetry.counter("c")
    telemetry.counter("c", 4)
    telemetry.gauge("g", 7.5)
    telemetry.gauge("g", 3.0)  # last-value wins
    telemetry.gauge_max("m", 2)
    telemetry.gauge_max("m", 9)
    telemetry.gauge_max("m", 4)  # high-water mark sticks
    for v in (0.75, 1.5, 3.0, 3.9, 0.0):
        telemetry.observe("h", v)
    snap = telemetry.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 3.0
    assert snap["gauges"]["m"] == 9
    h = snap["histograms"]["h"]
    assert h["count"] == 5
    assert h["sum"] == pytest.approx(9.15)
    assert h["min"] == 0.0 and h["max"] == 3.9
    # log2 buckets keyed by upper-bound exponent: [0.5,1) -> "0",
    # [1,2) -> "1", [2,4) -> "2"; non-positive values share the explicit
    # low overflow bucket.
    assert h["buckets"] == {"0": 1, "1": 1, "2": 2, "<=-32": 1}
    # The clamped ends declare themselves instead of impersonating a
    # nominal range.
    telemetry.observe("wide", 2.0**45)
    telemetry.observe("wide", 2.0**-40)
    wide = telemetry.snapshot()["histograms"]["wide"]["buckets"]
    assert wide == {">=31": 1, "<=-32": 1}


def test_disabled_sites_record_nothing(monkeypatch):
    telemetry.counter("c")
    telemetry.gauge("g", 1)
    telemetry.gauge_max("m", 1)
    telemetry.observe("h", 1)
    with telemetry.span("s"):
        pass
    # The host-phase spans of a merge (prepare, stage, census, put) too.
    monkeypatch.setenv("PERITEXT_MERGE_WINDOW_MIN", "16")
    genesis, c1, c2 = _author_stream()
    uni = TpuUniverse(["r0", "r1"], capacity=64)
    uni.apply_changes({"r0": [genesis], "r1": [genesis]})
    uni.apply_changes({"r0": [c1], "r1": [c2]})
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    # A span entered while enabled=False is the null span: no histogram.
    assert snap["histograms"] == {}


def test_summary_is_compact_and_selective():
    telemetry.enable()
    assert telemetry.summary() == {}  # nothing happened, nothing claimed
    telemetry.counter("ingest.launches", 3)
    telemetry.counter("ingest.path.delta", 2)
    telemetry.counter("ingest.path.scan", 1)
    telemetry.counter("faults.device_launch.failed", 2)
    telemetry.gauge_max("queue.depth_max", 17)
    s = telemetry.summary()
    assert s["launches"] == 3
    assert s["merge_path"] == {"delta": 2, "scan": 1}
    assert s["queue_depth_max"] == 17
    assert s["faults"] == {"device_launch.failed": 2}
    assert "degraded_batches" not in s


# ---------------------------------------------------------------------------
# Tracer: schema, nesting, thread tagging, env activation
# ---------------------------------------------------------------------------


def test_span_nesting_and_thread_tags(tmp_path):
    trace = str(tmp_path / "trace.jsonl")
    telemetry.enable(trace=trace)
    with telemetry.span("outer", kind="test"):
        with telemetry.span("inner"):
            time.sleep(0.002)
    t = threading.Thread(target=lambda: telemetry.span("other-thread").__enter__().__exit__())
    t.start()
    t.join()
    telemetry.flush_trace()
    assert assert_chrome_trace(trace) == 3
    events = [json.loads(l) for l in open(trace).read().splitlines()]
    by_name = {e["name"]: e for e in events if e["ph"] == "X"}
    outer, inner = by_name["outer"], by_name["inner"]
    # Nesting: inner sits inside outer on the same thread's timeline.
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"kind": "test"}
    assert by_name["other-thread"]["tid"] != outer["tid"]
    # Spans also land in the registry as duration histograms.
    hists = telemetry.snapshot()["histograms"]
    assert hists["span.outer.seconds"]["count"] == 1
    assert hists["span.inner.seconds"]["max"] <= hists["span.outer.seconds"]["max"]


def test_env_activation_and_exit_dump(tmp_path, monkeypatch):
    trace = tmp_path / "env.jsonl"
    metrics = tmp_path / "env-metrics.json"
    monkeypatch.setenv("PERITEXT_TRACE", str(trace))
    monkeypatch.setenv("PERITEXT_METRICS", str(metrics))
    telemetry._activate_from_env()  # what import does
    assert telemetry.enabled
    assert telemetry.trace_path() == str(trace)
    telemetry.counter("env.counter", 2)
    with telemetry.span("env.span"):
        pass
    telemetry._at_exit()  # what the registered atexit hook does
    assert_chrome_trace(str(trace))
    dumped = json.loads(metrics.read_text())
    assert dumped["counters"]["env.counter"] == 2
    assert "summary" in dumped and "histograms" in dumped


# ---------------------------------------------------------------------------
# The overhead contract (disabled path)
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_and_allocation_free():
    assert not telemetry.enabled
    # One shared null singleton: zero allocation per disabled span.
    assert telemetry.span("a") is telemetry.span("b")
    # The guarded-site pattern allocates nothing at all while disabled.
    t = telemetry
    for _ in range(64):  # warm every code path before measuring
        if t.enabled:
            t.counter("x")
        t.observe("y", 1.0)
        t.span("z")
        t.record("r")
        t.flow_point(None)
        t.flow_steps()
        t.flowing(())
        t.flow_keep()
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(1000):
        if t.enabled:
            t.counter("x")
        t.observe("y", 1.0)
        t.gauge_max("g", 2.0)
        t.span("z")
        # The causal-flow + flight-recorder sites share the contract:
        # guarded mint, None-propagating points, null flowing context,
        # recorder no-op — none may allocate while disabled.  The ISSUE 13
        # additions (tail-keep marking, the SLO feed path inside
        # counter/observe — exercised above with no sinks installed) ride
        # the same contract.
        ctx = t.flow("f") if t.enabled else None
        t.flow_point(ctx)
        t.flow_steps()
        t.flowing(())
        t.flow_keep()
        t.record("r")
        # The merge path's host-phase sites (ops/universe.py).
        with t.span("ingest.prepare"):
            pass
        with t.span("ingest.stage"):
            pass
        with t.span("ingest.put"):
            pass
    delta = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    assert delta < 16 * 1024, f"disabled telemetry path allocated {delta} bytes"


def test_disabled_path_micro_overhead_bounded():
    """Relative (not wall-clock) bound: the guarded site — one module
    attribute check — must stay within a small constant factor of an empty
    call, under best-of-N mins so background load cannot flake it."""
    assert not telemetry.enabled
    t = telemetry

    def guarded_site():
        if t.enabled:
            t.counter("x")

    def empty_call():
        pass

    site_best = min(timeit_repeat(guarded_site, number=20000, repeat=7))
    base_best = min(timeit_repeat(empty_call, number=20000, repeat=7))
    # An attribute check on top of call overhead: ~1-2x empty in practice;
    # 8x + absolute slack keeps a loaded 1-core box from flaking this.
    assert site_best < base_best * 8 + 0.01, (site_best, base_best)


# ---------------------------------------------------------------------------
# Registry thread-safety under the ChangeQueue timer thread
# ---------------------------------------------------------------------------


def test_no_lost_increments_under_timer_and_foreground_threads(tmp_path):
    trace = str(tmp_path / "threads.jsonl")
    telemetry.enable(trace=trace)
    flushed = []
    flushed_lock = threading.Lock()

    def handler(changes):
        with flushed_lock:
            flushed.extend(changes)

    q = ChangeQueue(handler, interval=0.001, name="telemetry-test-queue")
    q.start()
    N, THREADS = 500, 4

    def hammer(tid):
        for i in range(N):
            telemetry.counter("hammer.count")
            telemetry.observe("hammer.hist", i + 1)
            with telemetry.span("hammer.span", tid=tid):
                q.enqueue((tid, i))

    threads = [threading.Thread(target=hammer, args=(k,)) for k in range(THREADS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        q.flush()
        with flushed_lock:
            if len(flushed) == N * THREADS:
                break
        time.sleep(0.002)
    q.drop()
    assert len(flushed) == N * THREADS

    snap = telemetry.snapshot()
    # No lost increments on either structure, from any thread.
    assert snap["counters"]["hammer.count"] == N * THREADS
    assert snap["histograms"]["hammer.hist"]["count"] == N * THREADS
    assert snap["histograms"]["span.hammer.span.seconds"]["count"] == N * THREADS
    # The queue's own instrumentation fired and stayed consistent: every
    # successful non-empty flush observed its depth, and the depths sum to
    # the total delivered changes.
    assert snap["counters"]["queue.flushes"] >= 1
    depth = snap["histograms"]["queue.flush_depth"]
    assert depth["count"] == snap["counters"]["queue.flushes"]
    assert depth["sum"] == N * THREADS
    assert snap["gauges"]["queue.depth_max"] >= 1
    # Tracer survived concurrent writers: every line still parses.
    telemetry.flush_trace()
    assert assert_chrome_trace(trace) >= N * THREADS


# ---------------------------------------------------------------------------
# Fault-stat mirroring under seeded chaos
# ---------------------------------------------------------------------------


def _genesis_change():
    author = Doc("author")
    change, _ = author.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0, "values": list("chaos")},
        ]
    )
    return change


def _chaos_workload(seed, tmp_path, run_tag):
    """A seeded multi-site chaos run; returns (plan.stats, counters)."""
    telemetry.reset()
    telemetry.enable()
    plan = (
        FaultPlan(seed=seed)
        .with_site("device_launch", fail=2)
        .with_site("pubsub_deliver", drop=0.4, dup=0.3, reorder=0.3)
        .with_site("queue_flush", fail=1)
        .with_site("log_append", fail=1)
        .with_site("checkpoint_write", corrupt=1)
        .with_site("doc_evict", fail=1)
        .with_site("doc_hydrate", fail=1)
    )
    with faults.injected(plan):
        # device_launch: 2 injected failures absorbed by the retry budget.
        uni = TpuUniverse(["r0"])
        uni.apply_changes({"r0": [_genesis_change()]})
        # pubsub_deliver: 30 publishes across two subscribers.
        pub = Publisher()
        received = []
        pub.subscribe("x", received.append)
        pub.subscribe("y", received.append)
        for i in range(30):
            pub.publish("z", i)
        # queue_flush: first flush fails (batch re-enqueued), second lands.
        q = ChangeQueue(lambda ch: None, name="chaos-queue")
        q.enqueue("a", "b")
        with pytest.raises(FaultError):
            q.flush()
        q.flush()
        # log_append: first append fails before mutation, retry succeeds.
        log = ChangeLog()
        change = _genesis_change()
        with pytest.raises(FaultError):
            log.append(change)
        log.append(change)
        # checkpoint_write: the corrupt-on-write drill consumes its event.
        save_universe(uni, str(tmp_path / f"snap-{run_tag}"))
        # doc_evict / doc_hydrate: each protocol fails once (rolled back),
        # then the retry lands — runtime/lifecycle.py.
        from peritext_tpu.runtime.lifecycle import (
            DocLifecycle, EvictionError, HydrationError,
        )
        from peritext_tpu.runtime.serve_shard import ShardedServePlane

        plane = ShardedServePlane(
            1, start=False, batch_target=64, deadline_ms=10**9,
            name=f"chaos-{run_tag}",
        )
        lc = DocLifecycle(
            plane, start=False, watermark=0,
            directory=str(tmp_path / f"lc-{run_tag}"),
        )
        plane.session("cs", "chaos-doc").submit([_genesis_change()])
        plane.drain()
        with pytest.raises(EvictionError):
            lc.evict("cs")
        lc.evict("cs")
        with pytest.raises(HydrationError):
            lc.hydrate("cs")
        lc.hydrate("cs")
        plane.close()
    stats = {site: dict(v) for site, v in plan.stats.items()}
    counters = telemetry.snapshot()["counters"]
    telemetry.reset()
    return stats, counters


@pytest.mark.chaos
def test_fault_stats_mirror_registry_exactly(tmp_path):
    stats_a, counters_a = _chaos_workload(11, tmp_path, "a")
    stats_b, counters_b = _chaos_workload(11, tmp_path, "b")
    # Determinism: same seed + call order ⇒ same fault schedule.
    assert stats_a == stats_b
    # Exact agreement: the mirrored faults.* counters ARE plan.stats
    # (zero-valued stat keys never mirror — nothing fired for them).
    expected = {
        f"faults.{site}.{key}": n
        for site, per_site in stats_a.items()
        for key, n in per_site.items()
        if n
    }
    mirror_a = {k: v for k, v in counters_a.items() if k.startswith("faults.")}
    mirror_b = {k: v for k, v in counters_b.items() if k.startswith("faults.")}
    assert mirror_a == expected
    assert mirror_b == expected
    # The workload actually exercised every site class.
    assert stats_a["device_launch"]["failed"] == 2
    assert stats_a["queue_flush"]["failed"] == 1
    assert stats_a["log_append"]["failed"] == 1
    assert stats_a["checkpoint_write"]["corrupted"] == 1
    assert sum(
        stats_a["pubsub_deliver"][k] for k in ("dropped", "duplicated", "reordered")
    ) > 0
    # And the resilience counters rode along.
    assert counters_a["ingest.launch_retries"] == 2
    assert counters_a["ingest.launch_failures"] == 2
    assert counters_a["queue.reenqueues"] == 2
    # Two successful launches: the bare universe genesis + the serving
    # plane's genesis drain in the lifecycle exercise (evict drains an
    # empty lane; hydrate restores from checkpoint, no replay launch).
    assert counters_a["ingest.launches"] == 2
    assert stats_a["doc_evict"]["failed"] == 1
    assert stats_a["doc_hydrate"]["failed"] == 1


# ---------------------------------------------------------------------------
# Instrumentation under the engine differential (the tier-1 trace leg)
# ---------------------------------------------------------------------------

_EDIT_OPS = [
    {"path": ["text"], "action": "insert", "index": 3, "values": list("XY")},
    {"path": ["text"], "action": "addMark", "startIndex": 0, "endIndex": 8,
     "markType": "strong"},
    {"path": ["text"], "action": "delete", "index": 1, "count": 2},
    {"path": ["text"], "action": "addMark", "startIndex": 2, "endIndex": 9,
     "markType": "em"},
]


def _author_stream():
    """Genesis + two concurrent changes, authored once by oracle writers."""
    alice, bob = Doc("alice"), Doc("bob")
    genesis, _ = alice.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0,
             "values": list("peritext telemetry")},
        ]
    )
    bob.apply_change(genesis)
    c1, _ = alice.change(_EDIT_OPS[:2])
    c2, _ = bob.change(_EDIT_OPS[2:])
    return [genesis, c1, c2]


def _patched_ingest(changes, mode=None):
    """One universe, two replicas, full stream; returns (patches, plane,
    texts, stats-subset)."""
    with patch_path_env(mode):
        uni = TpuUniverse(["r0", "r1"])
        out = []
        for change in changes:
            got = uni.apply_changes_with_patches({"r0": [change], "r1": [change]})
            out.append(got)
        return out, device_plane(uni), uni.texts()


def test_ingest_byte_identical_with_telemetry_on_and_off(tmp_path):
    changes = _author_stream()
    assert not telemetry.enabled
    patches_off, plane_off, texts_off = _patched_ingest(changes)
    telemetry.enable(trace=str(tmp_path / "onoff.jsonl"))
    patches_on, plane_on, texts_on = _patched_ingest(changes)
    telemetry.flush_trace()
    assert patches_on == patches_off
    assert texts_on == texts_off
    for f in STATE_FIELDS:
        assert (plane_on[f] == plane_off[f]).all(), f"device plane differs at {f}"
    assert_chrome_trace(str(tmp_path / "onoff.jsonl"))


def test_trace_enabled_patch_path_differential(tmp_path):
    """The delta-vs-scan engine differential with tracing live end to end:
    instrumentation breakage in either path (or in the tracer) fails
    tier-1 here."""
    changes = _author_stream()
    trace = str(tmp_path / "diff.jsonl")
    telemetry.enable(trace=trace)
    patches_delta, plane_delta, _ = _patched_ingest(changes, mode=None)
    patches_scan, plane_scan, _ = _patched_ingest(changes, mode="scan")
    telemetry.flush_trace()
    assert patches_delta == patches_scan
    for f in STATE_FIELDS:
        assert (plane_delta[f] == plane_scan[f]).all()
    counters = telemetry.snapshot()["counters"]
    # Both paths were actually taken, and every launch was counted.
    assert counters["ingest.path.delta"] >= 1
    assert counters["ingest.path.scan"] >= 1
    assert counters["ingest.launches"] == counters["ingest.launch_attempts"]
    assert counters["ingest.h2d_bytes"] > 0
    assert counters["ingest.d2h_bytes"] > 0
    assert assert_chrome_trace(trace) > 0


def test_trace_enabled_tpu_vs_oracle_differential(tmp_path):
    """TpuDoc vs oracle Doc on the same concurrent edit, traced."""
    trace = str(tmp_path / "engines.jsonl")
    telemetry.enable(trace=trace)
    pairs = {"oracle": (Doc("a"), Doc("b")), "tpu": (TpuDoc("a"), TpuDoc("b"))}
    spans = {}
    for name, (d1, d2) in pairs.items():
        genesis, _ = d1.change(
            [
                {"path": [], "action": "makeList", "key": "text"},
                {"path": ["text"], "action": "insert", "index": 0,
                 "values": list("peritext telemetry")},
            ]
        )
        d2.apply_change(genesis)
        c1, _ = d1.change(_EDIT_OPS[:2])
        c2, _ = d2.change(_EDIT_OPS[2:])
        d1.apply_change(c2)
        d2.apply_change(c1)
        s1 = d1.get_text_with_formatting(["text"])
        s2 = d2.get_text_with_formatting(["text"])
        assert s1 == s2, f"{name} replicas diverged"
        spans[name] = s1
    assert spans["tpu"] == spans["oracle"]
    telemetry.flush_trace()
    assert assert_chrome_trace(trace) > 0
    counters = telemetry.snapshot()["counters"]
    # Only the TpuDoc engine routes through the instrumented change()
    # (genesis + one concurrent change per writer = 3).
    assert counters["doc.local_changes"] == 3
    hists = telemetry.snapshot()["histograms"]
    assert hists["span.doc.change.seconds"]["count"] == 3


# ---------------------------------------------------------------------------
# The host phases before a launch: sibling spans, in the profiler's trace too
# ---------------------------------------------------------------------------

HOST_PHASES = ("ingest.prepare", "ingest.stage", "ingest.window_census", "ingest.put")


def _span_counts():
    hists = telemetry.snapshot()["histograms"]
    return {
        name: hists.get(f"span.{name}.seconds", {}).get("count", 0)
        for name in HOST_PHASES
    }


def _tiny_fleet(monkeypatch):
    """Two replicas at a capacity the window census engages at, and the
    genesis + two concurrent changes to feed them."""
    monkeypatch.setenv("PERITEXT_MERGE_WINDOW_MIN", "16")
    return TpuUniverse(["r0", "r1"], capacity=64), _author_stream()


def test_apply_changes_spans_each_host_phase_once(monkeypatch):
    uni, (genesis, c1, c2) = _tiny_fleet(monkeypatch)
    telemetry.enable()
    uni.apply_changes({"r0": [genesis], "r1": [genesis]})
    # Genesis: the census fast-rejects an empty document before its span.
    assert _span_counts() == {
        "ingest.prepare": 1, "ingest.stage": 1,
        "ingest.window_census": 0, "ingest.put": 1,
    }
    uni.apply_changes({"r0": [c1], "r1": [c2]})
    assert _span_counts() == {name: 2 if name != "ingest.window_census" else 1
                              for name in HOST_PHASES}


def test_host_phase_spans_on_the_profiler_host_plane(tmp_path, monkeypatch):
    """Under a profiler session the spans are /host:CPU events of the same
    bare name, one each, and siblings: each ends before the next begins."""
    import glob

    import jax
    from jax.profiler import ProfileData

    uni, (genesis, c1, c2) = _tiny_fleet(monkeypatch)
    telemetry.enable()
    uni.apply_changes({"r0": [genesis], "r1": [genesis]})
    jax.block_until_ready(uni.states)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        uni.apply_changes({"r0": [c1], "r1": [c2]})
        jax.block_until_ready(uni.states)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for ev in line.events
        if ev.name in HOST_PHASES
    ]
    events.sort(key=lambda ev: ev[1])
    assert [name for name, _, _ in events] == list(HOST_PHASES)
    for (_, _, end), (_, start, _) in zip(events, events[1:]):
        assert end <= start


def test_serve_sites_disabled_record_nothing():
    """The serving plane's telemetry sites share the overhead contract:
    with collection off, a full submit/flush/resolve cycle must leave the
    registry empty (every site guards on the one `telemetry.enabled`
    attribute) while the plane's local stats still count."""
    from peritext_tpu.runtime.serve import ServePlane

    assert not telemetry.enabled
    changes = _author_stream()
    uni = TpuUniverse(["r0"])
    plane = ServePlane(uni, start=False, batch_target=8)
    s = plane.session("s0", replica="r0", record_stream=True)
    for change in changes:
        s.submit([change])
    assert plane.drain() == 0
    snap = telemetry.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {}
    assert plane.stats["flushes"] >= 1
    assert telemetry.summary() == {}


def test_serve_summary_section_rides_summary():
    telemetry.enable()
    telemetry.counter("serve.flushes", 3)
    telemetry.counter("serve.shed", 2)
    telemetry.gauge_max("serve.depth_max", 9)
    s = telemetry.summary()
    assert s["serve"]["flushes"] == 3
    assert s["serve"]["shed"] == 2
    assert s["serve"]["depth_max"] == 9


def test_compile_cache_counters_keyed_per_shard():
    """ISSUE 11 satellite: a sharded plane's compile-cache tallies are
    keyed per shard (serve.shard.<i>.compile_cache_*) AND the plane-global
    aggregate still counts every flush, so the shape-bucketing win stays
    attributable shard by shard.  An unsharded plane emits no shard keys."""
    from peritext_tpu.runtime.serve import ServePlane
    from peritext_tpu.runtime.serve_shard import ShardedServePlane

    telemetry.enable()
    changes = _author_stream()
    plane = ShardedServePlane(2, start=False, batch_target=8)
    s0 = plane.session("s0", replica="r0")
    s1 = plane.session("s1", replica="r1")
    s0.submit(changes)
    s1.submit([dict(c) for c in changes])
    assert plane.drain() == 0
    counters = telemetry.snapshot()["counters"]
    for i, shard in enumerate(plane.shards):
        per_shard = sum(
            counters.get(f"serve.shard.{i}.compile_cache_{k}", 0)
            for k in ("hit", "miss")
        )
        assert per_shard == shard.plane.stats["flushes"]
        assert (
            counters.get(f"serve.shard.{i}.compile_cache_miss", 0)
            == shard.plane.stats["compile_cache_misses"]
        )
    aggregate = counters.get("serve.compile_cache_hit", 0) + counters.get(
        "serve.compile_cache_miss", 0
    )
    assert aggregate == plane.stats["flushes"]
    # The summary's serve section carries the per-shard keys too.
    assert any(
        k.startswith("shard.") for k in telemetry.summary()["serve"]
    )
    # Unsharded control: same counters, no shard keys.
    telemetry.reset()
    telemetry.enable()
    uni = TpuUniverse(["r0"])
    flat = ServePlane(uni, start=False, batch_target=8)
    fs = flat.session("s0", replica="r0")
    fs.submit([dict(c) for c in changes])
    assert flat.drain() == 0
    counters = telemetry.snapshot()["counters"]
    assert not any(k.startswith("serve.shard.") for k in counters)
    assert (
        counters.get("serve.compile_cache_hit", 0)
        + counters.get("serve.compile_cache_miss", 0)
        == flat.stats["flushes"]
    )


def test_degraded_ingest_counts_in_registry():
    telemetry.enable()
    changes = _author_stream()
    uni = TpuUniverse(["r0"])
    uni.apply_changes({"r0": [changes[0]]})
    # Exhaust the whole retry budget: ingest degrades to the oracle path.
    with faults.injected(FaultPlan().with_site("device_launch", fail=10)):
        uni.apply_changes({"r0": changes[1:]})
    assert uni.stats["degraded_batches"] == 1
    counters = telemetry.snapshot()["counters"]
    assert counters["ingest.degraded_batches"] == 1
    assert counters["ingest.path.degraded"] == 1
    assert counters["ingest.launch_failures"] == 3  # 1 + retries(2)
