"""Cohort programs compiled before traffic (runtime/serve.py
``ServePlane.warm``; ops/universe.py ``warm_patched``; runtime/serve_shard.py
``ShardedServePlane.warm`` / ``open_sessions``).

A closed loop of one change in flight per session, on four shards over
four virtual devices, launches only programs ``warm()`` compiled: no
``serve.cold_shapes`` and no jit compile after it; every session's
patches equal the oracle's under the warmed shapes; the shards' launches
observe ``serve.launches_in_flight`` once each, within [1, shards]."""
import json

import jax
import pytest

from peritext_tpu.oracle import Doc
from peritext_tpu.ops import TpuUniverse
from peritext_tpu.runtime import telemetry
from peritext_tpu.runtime.serve import ROW_BUCKET, cohort_shape_key
from peritext_tpu.runtime.serve_shard import ShardedServePlane
from peritext_tpu.testing import window_env

from test_serve import author_stream

SHARDS = 4
PER_SHARD = 2
ROUNDS = 6


@pytest.fixture(scope="module")
def served():
    """The closed loop, run once: geneses loaded into the shard
    universes, ``warm()``, then ROUNDS rounds in which every session sends
    its next change and waits for the answer."""
    saved = (telemetry.enabled, telemetry._registry)
    telemetry._registry = telemetry.Registry()
    telemetry.enabled = True
    n = SHARDS * PER_SHARD
    streams = [author_stream(f"w{i}", ROUNDS, seed=100 + i) for i in range(n)]
    names = [f"d{i}" for i in range(n)]
    plane = ShardedServePlane(
        SHARDS, devices=jax.devices()[:SHARDS], capacity=256, max_mark_ops=64,
        batch_target=PER_SHARD, deadline_ms=20.0,
    )
    sessions = plane.open_sessions(
        [(f"s{i}", names[i]) for i in range(n)], record_stream=True
    )
    for i in range(n):
        plane.universe_of(names[i]).apply_changes({names[i]: [streams[i][0]]})
    warmed = plane.warm()
    compiles = []

    def listen(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for k in range(1, ROUNDS + 1):
            subs = [s.submit([streams[i][k]]) for i, s in enumerate(sessions)]
            for sub in subs:
                sub.result(timeout=300)
        plane.flush_and_wait(timeout=300)
        out = {
            "compiles": len(compiles),
            "warmed": warmed,
            "stats": plane.stats,
            "snapshot": telemetry.snapshot(),
            "logs": [list(s.patch_log) for s in sessions],
            "streams": streams,
            "shards": [s.shard for s in sessions],
        }
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        plane.close()
        telemetry.enabled, telemetry._registry = saved
    return out


def test_warm_closes_the_cohort_shapes(served):
    assert served["warmed"] >= SHARDS  # the full-table merge on every shard
    assert served["stats"]["cold_shapes"] == 0
    assert served["snapshot"]["counters"].get("serve.cold_shapes", 0) == 0
    assert served["compiles"] == 0
    assert served["shards"] == [i % SHARDS for i in range(SHARDS * PER_SHARD)]


def test_patches_equal_the_oracle_under_raised_floors(served):
    for stream, log in zip(served["streams"], served["logs"]):
        doc = Doc("oracle")
        doc.apply_change(stream[0])
        expect = [p for c in stream[1:] for p in doc.apply_change(c)]
        assert json.dumps(log, sort_keys=True) == json.dumps(expect, sort_keys=True)


def test_launches_in_flight_observed_once_per_launch(served):
    h = served["snapshot"]["histograms"]["serve.launches_in_flight"]
    launches = served["stats"]["flushes"] + served["stats"]["flush_failures"]
    assert h["count"] == launches > 0
    assert 1 <= h["min"] and h["max"] <= SHARDS


def test_cold_launch_is_counted():
    """A launch outside the warmed set (a plane never warmed) counts."""
    plane = ShardedServePlane(1, start=False, batch_target=4)
    s = plane.session("s", replica="r")
    s.submit(author_stream("cold", 1)[:2])
    assert plane.drain() == 0
    assert plane.stats["cold_shapes"] >= 1


def test_cohort_shape_key_floors():
    uni = TpuUniverse(["a", "b"])
    one = {"a": [{"ops": [{"action": "set"}]}]}
    eight = {"b": [{"ops": [{"action": "addMark"}] * ROW_BUCKET}]}
    assert cohort_shape_key(uni, one) == cohort_shape_key(uni, eight)
    nine = {"a": [{"ops": [{"action": "set"}] * (ROW_BUCKET + 1)}]}
    assert cohort_shape_key(uni, nine) != cohort_shape_key(uni, one)


def test_open_sessions_places_like_one_at_a_time():
    specs = [(f"s{i}", f"r{i}") for i in range(5)]
    bulk = ShardedServePlane(2, start=False, universe_factory=lambda ids, k: TpuUniverse(ids))
    one = ShardedServePlane(2, start=False, universe_factory=lambda ids, k: TpuUniverse(ids))
    got = bulk.open_sessions(specs)
    for n, r in specs:
        one.session(n, r)
    assert [s.shard for s in got] == [one.shard_of(r) for _, r in specs]
    for a, b in zip(bulk.shards, one.shards):
        assert a.real == b.real
        assert len(a.universe.replica_ids) == len(b.universe.replica_ids)
        assert a.universe.replica_ids[: len(a.real)] == b.universe.replica_ids[: len(b.real)]
    with pytest.raises(ValueError):
        bulk.open_sessions([("s0", "other")])


def _typed(doc, index, chars):
    change, _ = doc.change(
        [{"path": ["text"], "action": "insert", "index": index, "values": list(chars)}]
    )
    return change


def test_only_a_warmed_universe_pads_to_the_warmed_shapes():
    """Unwarmed, a text-only batch launches its own mark-free program and
    the winner cache may persist; after ``warm_patched`` the same kind of
    batch launches a warmed program, marked variant included."""
    doc = Doc("w")
    genesis, _ = doc.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0, "values": list("warm")},
        ]
    )
    uni = TpuUniverse(["a"])
    uni.apply_changes({"a": [genesis]})
    uni.apply_changes_with_patches({"a": [_typed(doc, 1, "x")]})
    (plain,) = uni.launch_keys
    assert dict(plain[5])["has_marks"] is False and plain[4] == 1
    assert not uni.warmed_keys
    assert uni.warm_patched() == len(uni.warmed_keys) >= 1
    uni.apply_changes_with_patches({"a": [_typed(doc, 2, "yz")]})
    (warm,) = uni.launch_keys
    assert warm in uni.warmed_keys
    assert dict(warm[5])["has_marks"] is True and warm[4] == 8
    assert uni._wcaches is None


def test_reach_bound_after_a_full_table_flush_is_warm():
    """A warmed universe whose mirror a full-table flush left stale runs
    the census's reach bound on its next flushes, rejecting a both-ends
    edit and letting a caret-local one through to a windowed launch, with
    no compile outside what ``warm_patched`` compiled and no cold shape."""
    doc = Doc("w")
    genesis, _ = doc.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0, "values": list("reach " * 50)},
        ]
    )

    def both_ends():
        length = sum(len(s["text"]) for s in doc.get_text_with_formatting(["text"]))
        change, _ = doc.change([
            {"path": ["text"], "action": "insert", "index": 0, "values": ["<"]},
            {"path": ["text"], "action": "insert", "index": length + 1, "values": [">"]},
        ])
        return change

    with window_env(True, min_cap="512"):
        uni = TpuUniverse(["a", "b"], capacity=512, max_mark_ops=64)
        uni.apply_changes({r: [genesis] for r in ("a", "b")})
        uni.warm_patched()
        assert ("window_reach", 2, 512) in uni.warmed_keys
        change = both_ends()
        uni.apply_changes_with_patches({r: [change] for r in ("a", "b")})
        assert uni._mirror_token is not uni.states  # full-table: stale
        compiles = []

        def listen(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(seconds)

        before = dict(uni.stats)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            for change in (both_ends(), _typed(doc, 150, "x")):
                uni.apply_changes_with_patches({r: [change] for r in ("a", "b")})
                assert set(uni.launch_keys) <= uni.warmed_keys
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
    grew = {k: uni.stats.get(k, 0) - before.get(k, 0) for k in (
        "window_reach_checks", "window_reach_rejects", "window_rebuilds", "windowed_launches")}
    assert grew == {"window_reach_checks": 2, "window_reach_rejects": 1,
                    "window_rebuilds": 1, "windowed_launches": 1}, grew
    assert compiles == []
