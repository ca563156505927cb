"""Benchmark workload generation and measurement.

The tracked metric (BASELINE.md): ops/sec merged over batched replica pairs.
A merge unit is one replica ingesting the op log its peer generated
concurrently — the applyChange hot path (micromerge.ts:499-514) that the
reference executes one op at a time with O(n) scans per op.

Workloads are generated once on the host with the oracle (which doubles as
the scalar baseline the speedup ratio is measured against), encoded to op
rows, tiled across the replica batch, and applied with one jit(vmap(scan))
launch per round.
"""
from __future__ import annotations

import os
import random
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from peritext_tpu.fuzz import (
    _random_add_mark,
    _random_delete,
    _random_insert,
    _text_len,
)
from peritext_tpu.ids import ActorRegistry
from peritext_tpu.ops import kernels as K
from peritext_tpu.ops.encode import AttrRegistry, encode_changes, split_rows
from peritext_tpu.ops.state import make_empty_state, stack_states
from peritext_tpu.oracle import Doc
from peritext_tpu.runtime.sync import apply_changes


def make_merge_workload(
    doc_len: int = 1000,
    ops_per_merge: int = 64,
    num_streams: int = 4,
    with_marks: bool = True,
    seed: int = 0,
    rounds: int = 1,
) -> Dict[str, Any]:
    """Base docs + concurrent op streams for a batched merge benchmark.

    Returns host-side artifacts: a genesis change building a doc of
    ``doc_len`` chars, and ``num_streams`` distinct concurrent change
    streams generated by oracle writer replicas.  Each writer emits
    ``rounds`` consecutive batches of ``ops_per_merge`` internal ops
    (``"rounds"``: per stream, the list of batches); ``"streams"`` is each
    writer's batches concatenated.  The device batch tiles these streams
    over R replicas.
    """
    rng = random.Random(seed)
    base = Doc("base")
    text = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ") for _ in range(doc_len))
    genesis, _ = base.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0, "values": list(text)},
        ]
    )
    stream_rounds: List[List[List[Dict[str, Any]]]] = []
    for s in range(num_streams):
        writer = Doc(f"writer{s}")
        writer.apply_change(genesis)
        batches: List[List[Dict[str, Any]]] = []
        for _ in range(rounds):
            changes: List[Dict[str, Any]] = []
            op_budget = ops_per_merge
            while op_budget > 0:
                kinds = ["insert", "remove"] + (["addMark"] if with_marks else [])
                kind = rng.choice(kinds)
                if kind == "insert":
                    op = _random_insert(rng, writer, 4)
                elif kind == "remove":
                    op = _random_delete(rng, writer)
                else:
                    op = _random_add_mark(rng, writer, [])
                if op is None:
                    continue
                if op["action"] == "insert":
                    cost = len(op["values"])
                    if cost == 0:
                        continue
                elif op["action"] == "delete":
                    cost = op["count"]
                else:
                    cost = 1
                cost = min(cost, op_budget)
                if op["action"] == "insert":
                    op["values"] = op["values"][:cost]
                elif op["action"] == "delete":
                    op["count"] = cost
                change, _ = writer.change([op])
                changes.append(change)
                op_budget -= cost
            batches.append(changes)
        stream_rounds.append(batches)
    return {
        "genesis": genesis,
        "streams": [[c for batch in batches for c in batch] for batches in stream_rounds],
        "rounds": stream_rounds,
        "text": text,
    }


def build_device_batch(
    workload: Dict[str, Any],
    num_replicas: int,
    capacity: int,
    max_mark_ops: int = 64,
) -> Dict[str, Any]:
    """Encode the workload into a stacked device batch for the fast path.

    Returns base states [R,...], split (text_ops, mark_ops) row tensors,
    ranks, and the per-replica op count.  Each replica gets one of the
    distinct streams, tiled round-robin.
    """
    actors = ActorRegistry()
    attrs = AttrRegistry()
    genesis_rows, _, _ = encode_changes([workload["genesis"]], actors, attrs)
    text_obj = workload["genesis"]["ops"][0]["opId"]  # the makeList op
    text_streams, mark_streams = [], []
    for stream in workload["streams"]:
        rows, _, _ = encode_changes(stream, actors, attrs, text_obj=text_obj)
        t, m = split_rows(rows)
        text_streams.append(t)
        mark_streams.append(m)
    max_text = max(max(r.shape[0] for r in text_streams), 1)
    max_mark = max(max(r.shape[0] for r in mark_streams), 1)

    # Build the base state once by applying the genesis ops on device via
    # the sorted path: the genesis document is one fused run placed in one
    # round (the scan alternative costs one step per character, which on
    # TPU also means a doc_len-step program to compile).  When the caller
    # explicitly selects the scan or Pallas path (BENCH_PATH / BENCH_PALLAS),
    # genesis must not depend on the sorted kernels either — the whole point
    # of those runs is independence from them — so it goes through the
    # fused scan instead.
    from peritext_tpu.ops.encode import fuse_insert_runs, pad_buffer, prepare_sorted_batch
    from peritext_tpu.ops.state import index_state

    ranks = np.zeros(64, np.int32)
    rk = actors.ranks()
    ranks[: len(rk)] = rk
    g_text, g_marks = split_rows(genesis_rows)
    base_b = stack_states([make_empty_state(capacity, max_mark_ops)])
    mark_rows = np.zeros((1, max(g_marks.shape[0], 1), K.OP_FIELDS), np.int32)
    mark_rows[0, : g_marks.shape[0]] = g_marks
    avoid_sorted = (
        os.environ.get("BENCH_PATH") == "scan" or os.environ.get("BENCH_PALLAS") == "1"
    )
    if avoid_sorted:
        fr, fb, _ = fuse_insert_runs(g_text)
        buf = pad_buffer(fb, max(fb.shape[0], K.MAX_RUN_LEN))
        base_b = K.merge_step_fused_batch(
            base_b,
            jax.numpy.asarray(fr[None, ...]),
            jax.numpy.asarray(mark_rows),
            jax.numpy.asarray(ranks),
            jax.numpy.asarray(buf[None, ...]),
        )
    else:
        sp = prepare_sorted_batch([g_text])
        base_b = K.merge_step_sorted_batch(
            base_b,
            jax.numpy.asarray(sp["text"]),
            jax.numpy.asarray(sp["rounds"]),
            sp["num_rounds"],
            jax.numpy.asarray(mark_rows),
            jax.numpy.asarray(ranks),
            jax.numpy.asarray(sp["bufs"]),
            sp["maxk"],
        )
    base = index_state(base_b, 0)
    np.asarray(base.length)  # completion barrier (host readback)

    states = stack_states([base] * num_replicas)
    text_ops = np.zeros((num_replicas, max_text, K.OP_FIELDS), np.int32)
    mark_ops = np.zeros((num_replicas, max_mark, K.OP_FIELDS), np.int32)
    total_ops = 0
    for r in range(num_replicas):
        t = text_streams[r % len(text_streams)]
        m = mark_streams[r % len(mark_streams)]
        text_ops[r, : t.shape[0]] = t
        mark_ops[r, : m.shape[0]] = m
        total_ops += t.shape[0] + m.shape[0]
    return {
        "states": states,
        "text_ops": text_ops,
        "mark_ops": mark_ops,
        "ranks": ranks,
        "total_ops": total_ops,
    }


def shift_op_ids(ops: np.ndarray, shift: int, genesis_max_op: int) -> np.ndarray:
    """Fresh op ids for a chained benchmark round.

    Op counters always shift; reference counters shift only when they point
    at ops inside the same stream (counters above the genesis range), so
    anchors into the base document stay valid.  The single implementation
    shared by every chained-round benchmark (throughput, latency, config 5)
    so their workloads can never skew apart.
    """
    out = np.array(ops)
    out[..., K.K_CTR] += (out[..., K.K_CTR] > 0) * shift
    for field in (K.K_REF_CTR, K.K_SCTR, K.K_ECTR):
        out[..., field] += (out[..., field] > genesis_max_op) * shift
    return out


def time_batched_merge(
    num_replicas: int = 1024,
    doc_len: int = 1000,
    ops_per_merge: int = 64,
    with_marks: bool = True,
    rounds: int = 8,
    seed: int = 0,
    mark_budget: int | None = None,
) -> Dict[str, Any]:
    """Measure batched merge throughput (BASELINE configs 3/4).

    Rounds are *chained*: each round's output state feeds the next round,
    and each round's op batch gets fresh op ids (counters shifted by a
    per-round offset, with intra-stream references shifted to match), so no
    layer — XLA or the runtime — can cache or overlap away the
    work, and the measurement models a continuous merge stream.

    ``mark_budget`` forces the mark-table capacity M (the bitset width is
    W = M/32), for A/B-ing the budget table's W=8 residency route
    (BASELINE.md): the same workload at M=256 vs the default sizing.  The
    run asserts the table never filled past the forced budget.
    """
    workload = make_merge_workload(doc_len, ops_per_merge, 4, with_marks, seed)
    genesis_max_op = workload["genesis"]["startOp"] + len(workload["genesis"]["ops"]) - 1
    capacity = 1
    while capacity < doc_len + (rounds + 1) * ops_per_merge + 8:
        capacity *= 2
    if mark_budget is not None:
        max_marks = mark_budget
    else:
        max_marks = 64
        while max_marks < (rounds + 1) * ops_per_merge + 32:
            max_marks *= 2
    batch = build_device_batch(workload, num_replicas, capacity, max_marks)

    # Per-round op-id shift masks (shift_op_ids' rule, expressed as a 0/1
    # mask so rounds can shift on device inside the jitted loop).
    def make_shift(ops):
        return shift_op_ids(ops, 1, genesis_max_op) - ops

    # Path selection: sort-based placement (production default), the
    # sequential fused scan (BENCH_PATH=scan), or the VMEM Pallas merge
    # (BENCH_PALLAS=1 — off by default until the Mosaic lowering has a
    # hardware verification pass, see ops/pallas_kernels.py).  All three
    # take the same fused workload so the comparison is one of kernels.
    use_pallas = os.environ.get("BENCH_PALLAS") == "1"
    use_scan = use_pallas or os.environ.get("BENCH_PATH") == "scan"
    if use_pallas:
        from peritext_tpu.ops.pallas_kernels import merge_step_pallas_full

    from peritext_tpu.ops.encode import prepare_sorted_batch

    # Fuse chained inserts so typing runs apply one-per-step (scan/Pallas
    # need the static MAX_RUN_LEN window; the sorted path fuses unbounded).
    sp = prepare_sorted_batch(
        [batch["text_ops"][r] for r in range(num_replicas)],
        max_run=K.MAX_RUN_LEN if use_scan else 0,
    )
    num_rounds, maxk = sp["num_rounds"], sp["maxk"]

    offset = 1_000_000
    text_d = jax.numpy.asarray(sp["text"])
    mark_d = jax.numpy.asarray(batch["mark_ops"])
    tshift_d = jax.numpy.asarray(make_shift(sp["text"]))
    mshift_d = jax.numpy.asarray(make_shift(batch["mark_ops"]))
    bufs_d = jax.numpy.asarray(sp["bufs"])
    rounds_d = jax.numpy.asarray(sp["rounds"])
    ranks_d = jax.numpy.asarray(batch["ranks"])
    states = batch["states"]

    @jax.jit
    def run_rounds(states, base):
        def body(st, i):
            shift = base + i * offset
            if use_pallas:
                st = merge_step_pallas_full(
                    st,
                    text_d + tshift_d * shift,
                    mark_d + mshift_d * shift,
                    ranks_d,
                    char_buf=bufs_d,
                )
            elif use_scan:
                st = K.merge_step_fused_vmapped(
                    st, text_d + tshift_d * shift, mark_d + mshift_d * shift, ranks_d, bufs_d
                )
            else:
                st = K.merge_step_sorted_batch(
                    st,
                    text_d + tshift_d * shift,
                    rounds_d,
                    num_rounds,
                    mark_d + mshift_d * shift,
                    ranks_d,
                    bufs_d,
                    maxk,
                )
            return st, 0

        final, _ = jax.lax.scan(
            body, states, jax.numpy.arange(rounds, dtype=jax.numpy.int32)
        )
        return final

    def run_and_sync(base):
        # Host readback: the rounds are done and their result reached
        # the host.
        out = run_rounds(states, jax.numpy.int32(base))
        np.asarray(out.length)
        return out

    run_and_sync(offset)  # warmup/compile with distinct op ids
    # Best-of-N timed passes (fresh op ids each pass, same initial states);
    # the pass count is reported alongside.  Two on the CPU, where host
    # scheduling swings a single pass by ~12%; one elsewhere unless
    # BENCH_BEST_OF asks otherwise.
    default_best = 2 if jax.devices()[0].platform == "cpu" else 1
    best_of = max(1, int(os.environ.get("BENCH_BEST_OF", str(default_best))))
    elapsed = float("inf")
    out = None
    for t in range(best_of):
        start = time.perf_counter()
        out = run_and_sync((t + 1) * (rounds + 2) * offset)
        elapsed = min(elapsed, time.perf_counter() - start)

    total_ops = batch["total_ops"] * rounds
    if mark_budget is not None:
        peak_marks = int(np.asarray(out.mark_count).max())
        assert peak_marks <= mark_budget, (
            f"forced mark budget {mark_budget} overflowed: table reached "
            f"{peak_marks} live ops — shrink rounds/ops_per_merge for this A/B"
        )
    return {
        "seconds": elapsed,
        "total_ops": total_ops,
        "ops_per_sec": total_ops / elapsed,
        "replicas": num_replicas,
        "doc_len": doc_len,
        "rounds": rounds,
        "max_marks": max_marks,
        "capacity": capacity,
        "best_of": best_of,
        "final_states": out,
    }


def time_merge_latency(
    doc_len: int = 10_000,
    ops_per_merge: int = 64,
    trials: int = 9,
    seed: int = 0,
) -> Dict[str, Any]:
    """p50 single-merge latency at the 10k-char doc shape (BASELINE's second
    tracked metric).

    One replica pair: a doc of ``doc_len`` chars ingests one concurrent
    ``ops_per_merge``-op batch per trial (fresh op ids each trial, chained
    state), timed individually with host-readback barriers; reports the
    median.
    """
    workload = make_merge_workload(doc_len, ops_per_merge, 2, True, seed)
    genesis_max_op = workload["genesis"]["startOp"] + len(workload["genesis"]["ops"]) - 1
    capacity = 1
    while capacity < doc_len + (trials + 2) * ops_per_merge + 8:
        capacity *= 2
    max_marks = 64
    while max_marks < (trials + 2) * ops_per_merge + 32:
        max_marks *= 2
    # The Pallas kernels process replicas in sublane blocks of 8; the
    # latency unit becomes one block of identical replicas (same single
    # launch, same critical path — the other 7 sublanes ride along).
    lat_replicas = 8 if os.environ.get("BENCH_PALLAS") == "1" else 1
    batch = build_device_batch(workload, lat_replicas, capacity, max_marks)

    from peritext_tpu.ops.encode import fuse_insert_runs, pad_buffer, prepare_sorted_batch

    # Measure the same path the throughput run uses (BENCH_PATH=scan and
    # BENCH_PALLAS runs must not attribute sorted-path latency).  The
    # launch-bound R=1 regime is exactly where the VMEM splice should win
    # (PROFILE_r04 conclusion 4 fix (b)): BENCH_PALLAS measures
    # merge_step_pallas here — the Pallas text kernel (the splice, VMEM-
    # resident across the whole op list) + the XLA mark tail; the full-
    # VMEM mark kernel does not fit at C=16384 ([8, 2C, 32] is 32 MiB).
    use_pallas = os.environ.get("BENCH_PALLAS") == "1"
    use_scan = use_pallas or os.environ.get("BENCH_PATH") == "scan"
    if use_pallas:
        from peritext_tpu.ops.pallas_kernels import merge_step_pallas_jit

        pallas_merge = merge_step_pallas_jit()
    marks_np = batch["mark_ops"]
    ranks_d = jax.numpy.asarray(batch["ranks"])
    states = batch["states"]
    if use_scan:
        fr, fb, _ = fuse_insert_runs(batch["text_ops"][0])
        text_np = np.repeat(fr[None, ...], lat_replicas, axis=0)
        buf0 = pad_buffer(fb, max(fb.shape[0], K.MAX_RUN_LEN))
        bufs_d = jax.numpy.asarray(np.repeat(buf0[None, ...], lat_replicas, axis=0))
        # All block lanes run replica 0's stream (identical work per lane).
        marks_np = np.repeat(batch["mark_ops"][0][None, ...], lat_replicas, axis=0)
    else:
        sp = prepare_sorted_batch([batch["text_ops"][0]])
        text_np = sp["text"]
        rounds_d = jax.numpy.asarray(sp["rounds"])
        bufs_d = jax.numpy.asarray(sp["bufs"])

    def merge(states, shift):
        # Only the shifted op rows change per trial; everything else is
        # uploaded once above.
        text = jax.numpy.asarray(shift_op_ids(text_np, shift, genesis_max_op))
        marks = jax.numpy.asarray(shift_op_ids(marks_np, shift, genesis_max_op))
        if use_pallas:
            out = pallas_merge(states, text, marks, ranks_d, bufs_d)
        elif use_scan:
            out = K.merge_step_fused_batch(states, text, marks, ranks_d, bufs_d)
        else:
            out = K.merge_step_sorted_batch(
                states, text, rounds_d, sp["num_rounds"], marks, ranks_d, bufs_d, sp["maxk"]
            )
        np.asarray(out.length)  # host readback barrier
        return out

    states = merge(states, 1_000_000)  # compile + warm
    latencies = []
    for t in range(trials):
        start = time.perf_counter()
        states = merge(states, (t + 2) * 1_000_000)
        latencies.append(time.perf_counter() - start)
    latencies.sort()
    return {
        "p50_ms": round(1e3 * latencies[len(latencies) // 2], 3),
        "min_ms": round(1e3 * latencies[0], 3),
        "doc_len": doc_len,
        "ops_per_merge": ops_per_merge,
        "trials": trials,
        "path": "pallas" if use_pallas else ("scan" if use_scan else "sorted"),
    }


def time_long_doc_flatten(
    doc_len: int = 1 << 16,
    num_replicas: int = 8,
    seq_shards: int = 2,
    rounds: int = 4,
    seed: int = 0,
) -> Dict[str, Any]:
    """Config-5 long-document axis: sequence-parallel materialization.

    Builds replicas with ``doc_len``-char documents plus overlapping marks,
    shards the capacity dimension over the ``seq`` mesh axis, and measures
    the explicit shard_map flatten (parallel/shard.py) — chars/sec
    materialized.  The same harness runs on a virtual CPU mesh (functional
    check) and on real slices (numbers).
    """
    import jax as _jax

    from peritext_tpu.parallel import flatten_sources_sp, make_mesh, shard_states

    capacity = 1
    while capacity < doc_len + 64:
        capacity *= 2
    workload = make_merge_workload(
        doc_len=doc_len, ops_per_merge=32, num_streams=2, with_marks=True, seed=seed
    )
    batch = build_device_batch(workload, num_replicas, capacity, 64)
    states = K.merge_step_batch(
        batch["states"],
        _jax.numpy.asarray(batch["text_ops"]),
        _jax.numpy.asarray(batch["mark_ops"]),
        _jax.numpy.asarray(batch["ranks"]),
    )

    devices = _jax.devices()
    n = min(len(devices), num_replicas * seq_shards)
    mesh = make_mesh(devices[: (n // seq_shards) * seq_shards], n // seq_shards, seq_shards)
    states = shard_states(states, mesh)
    flatten = flatten_sources_sp(mesh)

    args = (states.deleted, states.bnd_def, states.bnd_mask, states.length)
    mask, has = flatten(*args)
    np.asarray(has)  # completion barrier (host readback)
    start = time.perf_counter()
    for _ in range(rounds):
        mask, has = flatten(*args)
    np.asarray(has)
    elapsed = time.perf_counter() - start
    chars = num_replicas * doc_len * rounds
    return {
        "seconds": elapsed,
        "chars_per_sec": chars / elapsed,
        "doc_len": doc_len,
        "replicas": num_replicas,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
    }


def _telemetry_counter(name: str) -> int:
    """Current value of a registry counter (0 while telemetry is off)."""
    from peritext_tpu.runtime import telemetry

    if not telemetry.enabled:
        return 0
    return int(telemetry.snapshot()["counters"].get(name, 0))


def time_patched_merge(
    num_replicas: int = 256,
    doc_len: int = 1000,
    ops_per_merge: int = 64,
    seed: int = 0,
    force_scan: bool = False,
    with_marks: bool = True,
    mode: str | None = None,
    readback: str | None = None,
) -> Dict[str, Any]:
    """End-to-end throughput of the PATCH-EMITTING ingest path.

    Unlike time_batched_merge (device kernels only, no patches), this runs
    the full TpuUniverse.apply_changes_with_patches API — gate, encode,
    device merge, record readback, host patch assembly — the path an editor
    fleet consumes (bridge.ts subscribe callbacks, bridge.ts:238-279).
    Measures one warmed round of R replicas each ingesting a fresh
    ``ops_per_merge``-op concurrent stream.  ``force_scan`` measures the
    interleaved per-op fallback for the A/B; ``mode="dense"`` pins the
    full-plane-carry sorted scan (the compact-delta A/B baseline).  The
    default leg explicitly CLEARS any ambient scan-forcing knobs so the
    A/B labels stay honest even under the scan-forced CI mode, and every
    leg restores the caller's environment afterwards.  ``readback`` pins
    the record transfer format ("compact"/"planes"; None clears the knob
    — the compact default); the measured ingest's D2H record bytes are
    stamped into the result from the telemetry tally when collection is
    on (None otherwise).
    """
    from peritext_tpu.ops import TpuUniverse
    from peritext_tpu.runtime import telemetry

    rng = random.Random(seed)
    writers, _, genesis = _patched_writers(doc_len, rng)
    stream, n_ops = _make_patched_stream(
        rng, writers, ops_per_merge, with_marks=with_marks
    )

    names = [f"r{i}" for i in range(num_replicas)]
    capacity = 1
    while capacity < doc_len + n_ops + 64:
        capacity *= 2
    from peritext_tpu.testing import patch_path_env, patch_readback_env

    with patch_path_env("scan" if force_scan else mode), patch_readback_env(readback):
        # Warm the jit caches with the SAME stream on a throwaway universe
        # (identical shapes -> the measured run is launch + assembly, no
        # compiles; duplicate-change dedup doesn't cross universes).
        def build():
            uni = TpuUniverse(names, capacity=capacity)
            uni.apply_changes_with_patches({n: [genesis] for n in names})
            return uni

        build().apply_changes_with_patches({n: list(stream) for n in names})
        uni = build()
        d2h0 = _telemetry_counter("ingest.d2h_bytes")
        start = time.perf_counter()
        out = uni.apply_changes_with_patches({n: list(stream) for n in names})
        elapsed = time.perf_counter() - start
        d2h = (
            _telemetry_counter("ingest.d2h_bytes") - d2h0
            if telemetry.enabled
            else None
        )
    total_ops = num_replicas * n_ops
    # Label from what actually ran: a deep stream can silently take the
    # interleaved fallback even on the sorted leg.
    fell_back = uni.stats["scan_fallbacks"] > 0
    return {
        "seconds": elapsed,
        "total_ops": total_ops,
        "ops_per_sec": total_ops / elapsed,
        "patches": sum(len(v) for v in out.values()),
        "replicas": num_replicas,
        "d2h_bytes": d2h,
        "readback_overflows": uni.stats.get("readback_overflows", 0),
        "path": "scan"
        if force_scan
        else (
            "scan_fallback"
            if fell_back
            else ("sorted_dense" if mode == "dense" else "sorted_delta")
        ),
    }


def _patched_writers(doc_len: int, rng: random.Random):
    """Synced oracle writers over a doc_len-char genesis (patched-bench
    stream generation)."""
    from peritext_tpu.testing import generate_docs

    text = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ") for _ in range(doc_len))
    docs, patches, genesis = generate_docs(text, 4)
    return docs, patches, genesis


def _local_op(rng: random.Random, writer, kind: str, hot: int, width: int):
    """One op with every index confined to the [hot, hot+width) hotspot —
    the editor-caret locality pattern (windowed-merge engagement needs the
    batch hull to stay well under the document)."""
    length = _text_len(writer)
    if length == 0:
        return None
    lo = min(hot, length - 1)
    hi = min(hot + width, length)
    if kind == "insert":
        idx = lo + rng.randrange(max(1, hi - lo))
        values = [
            rng.choice("abcdefghijklmnopqrstuvwxyz ")
            for _ in range(rng.randrange(1, 7))
        ]
        return {"path": ["text"], "action": "insert", "index": min(idx, length),
                "values": values}
    if kind == "remove":
        if hi <= lo:
            return None
        return {"path": ["text"], "action": "delete",
                "index": lo + rng.randrange(hi - lo), "count": 1}
    start = lo + rng.randrange(max(1, hi - lo))
    end = min(start + rng.randrange(1, max(2, width // 4)), length)
    if end <= start:
        return None
    return {"path": ["text"], "action": "addMark", "startIndex": start,
            "endIndex": end, "markType": rng.choice(["strong", "em"])}


def _make_patched_stream(
    rng: random.Random,
    writers,
    ops_per_merge: int,
    with_marks: bool = True,
    min_marks: int = 0,
    locality: int = 0,
) -> tuple:
    """One causally-consecutive concurrent stream from the shared writers
    (THE stream generator for every patched bench — merge and fleet).

    ``min_marks`` keeps generating addMark ops past the op budget until the
    stream carries that many mark rows (a by-chance markless round would
    take the static mark-free fast path and skew a cache measurement).

    ``locality`` > 0 confines each stream's edit indices to one
    rng-drawn ``locality``-char hotspot (a fresh hotspot per stream) — the
    realistic editor-caret pattern, and the regime where the
    frontier-bounded window merge engages (CONFIG6_LOCALITY; 0 keeps the
    historical uniform-position baseline)."""
    if min_marks and not with_marks:
        raise ValueError("min_marks requires with_marks (pure-typing streams "
                         "must not be topped up with mark ops)")
    kinds = ["insert", "insert", "remove"] + (["addMark"] if with_marks else [])
    hot = None
    if locality:
        length0 = _text_len(writers[0])
        hot = rng.randrange(max(1, length0 - locality))
    stream, n_ops, n_marks = [], 0, 0
    while n_ops < ops_per_merge or n_marks < min_marks:
        writer = writers[rng.randrange(len(writers))]
        kind = (
            "addMark"
            if n_ops >= ops_per_merge
            else rng.choice(kinds)
        )
        if locality:
            op = _local_op(rng, writer, kind, hot, locality)
        else:
            op = (
                _random_insert(rng, writer, 6)
                if kind == "insert"
                else _random_delete(rng, writer)
                if kind == "remove"
                else _random_add_mark(rng, writer, [])
            )
        if op is None:
            continue
        change, _ = writer.change([op])
        n_ops += len(change["ops"])
        n_marks += sum(
            1 for o in change["ops"] if o["action"] in ("addMark", "removeMark")
        )
        stream.append(change)
        for other in writers:
            if other is not writer:
                other.apply_change(change)
    return stream, n_ops


def time_patched_fleet(
    num_replicas: int = 256,
    doc_len: int = 1000,
    ops_per_merge: int = 64,
    rounds: int = 4,
    seed: int = 0,
    mode: str | None = None,
    readback: str | None = None,
    locality: int = 0,
) -> Dict[str, Any]:
    """The editor-fleet steady state: repeated patched ingests on ONE
    universe — the workload the persisted winner cache exists for.

    Round 1 ingests on a fresh universe (cache COLD: the dominance init
    runs, per PROFILE_r04 conclusion 3); rounds 2..N reuse the universe's
    threaded cache (WARM: no init, the cache rides the merge).  Reports
    both regimes plus the same streams through the no-patch ingest at the
    same shape — the cache's job is to close the patched-vs-no-patch gap
    in exactly this steady state.

    Every round's stream is causally consecutive (the shared oracle
    writers keep typing), so shapes differ per round; all shapes for both
    program variants (cold init / threaded cache) are compiled on a
    throwaway universe before anything is timed.

    ``mode="dense"`` pins the full-plane-carry mark scan (the
    compact-delta A/B baseline); the default leg clears the knobs and
    measures the delta scan.  Identical streams per ``seed`` either way,
    so BENCH_PATCHES=ab's dense-vs-delta legs compare the same work.
    ``readback`` pins the record transfer format (compact run tables vs
    the full planes; None = the compact default); cold/warm D2H record
    bytes are stamped from the telemetry tally when collection is on.
    """
    from peritext_tpu.ops import TpuUniverse
    from peritext_tpu.runtime import telemetry
    from peritext_tpu.testing import patch_path_env, patch_readback_env

    rng = random.Random(seed)
    writers, _, genesis = _patched_writers(doc_len, rng)
    # min_marks=2: every round must carry mark rows, or the mark-free fast
    # path would reset the very steady state under measurement.
    made = [
        _make_patched_stream(
            rng, writers, ops_per_merge, min_marks=2, locality=locality
        )
        for _ in range(rounds)
    ]
    streams = [s for s, _ in made]
    ops_per_round = [n for _, n in made]

    names = [f"r{i}" for i in range(num_replicas)]
    capacity = 1
    while capacity < doc_len + sum(ops_per_round) + 64:
        capacity *= 2

    def build():
        uni = TpuUniverse(names, capacity=capacity)
        uni.apply_changes_with_patches({n: [genesis] for n in names})
        return uni

    with patch_path_env(mode), patch_readback_env(readback):
        # Compile pass: the throwaway universe walks the same round
        # sequence, so both the cold-init and threaded-cache program
        # variants exist for every round's shape before timing starts.
        throwaway = build()
        for stream in streams:
            throwaway.apply_changes_with_patches({n: list(stream) for n in names})

        uni = build()
        round_s = []
        round_d2h = []
        for stream in streams:
            d2h0 = _telemetry_counter("ingest.d2h_bytes")
            start = time.perf_counter()
            uni.apply_changes_with_patches({n: list(stream) for n in names})
            round_s.append(time.perf_counter() - start)
            round_d2h.append(_telemetry_counter("ingest.d2h_bytes") - d2h0)
        fell_back = uni.stats["scan_fallbacks"] > 0
        if (
            mode != "scan"
            and not fell_back
            and uni.stats.get("windowed_launches", 0) == 0
        ):
            # An all-windowed steady state never materializes the FULL
            # cache plane (each windowed launch inits over its own window
            # — already O(window)); the persistence contract applies to
            # full-table rounds only.
            assert uni._wcaches is not None, "winner cache not persisted after rounds"

        # No-patch leg: identical streams, same universe lifecycle, through
        # the plain ingest (state only, no patch records).
        nop = build()
        for stream in streams:  # compile pass
            nop.apply_changes({n: list(stream) for n in names})
        nop = build()
        nop_s = []
        for stream in streams:
            start = time.perf_counter()
            nop.apply_changes({n: list(stream) for n in names})
            # apply_changes dispatches asynchronously (the patched leg is
            # implicitly barriered by its record readback); a host readback
            # is the only honest stop-the-clock on every backend.
            np.asarray(nop.states.length)
            nop_s.append(time.perf_counter() - start)

    warm_ops = num_replicas * sum(ops_per_round[1:])
    warm_sec = sum(round_s[1:])
    cold = num_replicas * ops_per_round[0] / round_s[0]
    warm = warm_ops / warm_sec
    nop_total = num_replicas * sum(ops_per_round)
    no_patch = nop_total / sum(nop_s)
    return {
        "replicas": num_replicas,
        "doc_len": doc_len,
        "rounds": rounds,
        "ops_per_round": ops_per_round,
        "patched_cold_ops_per_sec": cold,
        "patched_warm_ops_per_sec": warm,
        "no_patch_ops_per_sec": no_patch,
        "warm_vs_no_patch": no_patch / warm,
        "warm_vs_cold_speedup": warm / cold,
        "cold_d2h_bytes": round_d2h[0] if telemetry.enabled else None,
        "warm_d2h_bytes": sum(round_d2h[1:]) if telemetry.enabled else None,
        "readback_overflows": uni.stats.get("readback_overflows", 0),
        "locality": locality,
        "windowed_launches": uni.stats.get("windowed_launches", 0),
        "window_fallbacks": uni.stats.get("window_fallbacks", 0),
        "path": "scan"
        if mode == "scan"
        else "scan_fallback"
        if fell_back
        else ("sorted_dense" if mode == "dense" else "sorted_delta"),
    }


def time_scalar_baseline(
    doc_len: int = 1000,
    ops_per_merge: int = 64,
    merges: int = 8,
    with_marks: bool = True,
    seed: int = 0,
) -> Dict[str, Any]:
    """The scalar reference-semantics engine running the same merge units.

    This is the stand-in for the reference implementation (which publishes no
    numbers and cannot run here — no node in the image): an O(n)-per-op
    scalar engine with identical semantics, measured on the same workload
    shape.
    """
    workload = make_merge_workload(doc_len, ops_per_merge, 4, with_marks, seed)
    streams = workload["streams"]
    total_ops = 0
    start = time.perf_counter()
    for i in range(merges):
        reader = Doc(f"reader{i}")
        reader.apply_change(workload["genesis"])
        stream = streams[i % len(streams)]
        apply_changes(reader, list(stream))
        total_ops += sum(len(c["ops"]) for c in stream)
    elapsed = time.perf_counter() - start
    return {"seconds": elapsed, "total_ops": total_ops, "ops_per_sec": total_ops / elapsed}


def time_streaming_ab(
    num_replicas: int = 2048,
    doc_len: int = 1000,
    ops_per_merge: int = 64,
    cohort: int = 512,
    with_marks: bool = True,
    seed: int = 0,
) -> Dict[str, Any]:
    """Resident vs streaming-cohort merge at a shape that fits both ways:
    the cohort-overhead measurement for the north-star route (BASELINE.md).

    Both legs run the identical one-round merge + convergence digest on the
    same batch with fresh op ids.  The resident leg starts (and stays)
    device-resident — the regime it represents; the streaming leg starts
    host-resident and pays both transfers (H2D states+ops, D2H states+
    digests) through ``stream_merge_sorted``.  The ratio is the streaming
    overhead a >residency population would pay per pass.
    """
    from peritext_tpu.ops.encode import prepare_sorted_batch
    from peritext_tpu.parallel.stream import stream_merge_sorted
    from peritext_tpu.schema import allow_multiple_array

    workload = make_merge_workload(doc_len, ops_per_merge, 4, with_marks, seed)
    genesis_max_op = workload["genesis"]["startOp"] + len(workload["genesis"]["ops"]) - 1
    capacity = 1
    while capacity < doc_len + 2 * ops_per_merge + 8:
        capacity *= 2
    max_marks = 64
    while max_marks < 2 * ops_per_merge + 32:
        max_marks *= 2
    batch = build_device_batch(workload, num_replicas, capacity, max_marks)
    sp = prepare_sorted_batch([batch["text_ops"][r] for r in range(num_replicas)])
    ranks_d = jax.numpy.asarray(batch["ranks"])
    multi_d = jax.numpy.asarray(allow_multiple_array())

    def fresh(arr, shift):
        return shift_op_ids(arr, shift, genesis_max_op)

    # -- resident leg: states live on device across launches ---------------
    states_dev = batch["states"]

    def resident(shift):
        out = K.merge_step_sorted_batch(
            states_dev,
            jax.numpy.asarray(fresh(sp["text"], shift)),
            jax.numpy.asarray(sp["rounds"]),
            sp["num_rounds"],
            jax.numpy.asarray(fresh(batch["mark_ops"], shift)),
            ranks_d,
            jax.numpy.asarray(sp["bufs"]),
            sp["maxk"],
        )
        dg = K.convergence_digest_batch(out, ranks_d, multi_d)
        return np.asarray(dg)  # host readback barrier

    resident(1_000_000)  # compile + warm
    start = time.perf_counter()
    dg_resident = resident(2_000_000)
    resident_s = time.perf_counter() - start

    # -- streaming leg: states live on host, cohorts stream ----------------
    states_np = jax.tree.map(np.asarray, batch["states"])

    def streaming(shift):
        return stream_merge_sorted(
            states_np,
            fresh(sp["text"], shift),
            sp["rounds"],
            sp["num_rounds"],
            fresh(batch["mark_ops"], shift),
            batch["ranks"],
            sp["bufs"],
            sp["maxk"],
            cohort=cohort,
        )

    streaming(1_000_000)  # compile + warm (cohort-shaped program)
    start = time.perf_counter()
    _, dg_stream, stats = streaming(2_000_000)
    stream_s = time.perf_counter() - start

    assert (dg_stream == dg_resident).all(), "streaming diverged from resident"
    total_ops = batch["total_ops"]
    return {
        "replicas": num_replicas,
        "doc_len": doc_len,
        "cohort": cohort,
        "n_cohorts": stats["n_cohorts"],
        "resident_seconds": resident_s,
        "streaming_seconds": stream_s,
        "resident_ops_per_sec": total_ops / resident_s,
        "streaming_ops_per_sec": total_ops / stream_s,
        "streaming_overhead": stream_s / resident_s,
        "total_ops": total_ops,
    }


# -- serving-plane A/B (naive per-change ingest vs continuous batching) -------


def _serve_author_sessions(sessions: int, rounds: int, changes_per_round: int,
                           doc_len: int, rng: random.Random):
    """Per-session oracle lineages: each session fronts its own replica and
    types its own document (the multi-tenant serving shape — independent
    docs behind one universe/plane).  Returns per-session change lists
    grouped by round (round 0 is the genesis)."""
    per_session: List[List[List[Dict[str, Any]]]] = []
    for s in range(sessions):
        writer = Doc(f"svc{s}")
        text = "".join(
            rng.choice("abcdefghijklmnopqrstuvwxyz ") for _ in range(doc_len)
        )
        genesis, _ = writer.change(
            [
                {"path": [], "action": "makeList", "key": "text"},
                {"path": ["text"], "action": "insert", "index": 0, "values": list(text)},
            ]
        )
        session_rounds: List[List[Dict[str, Any]]] = [[genesis]]
        for _ in range(rounds):
            batch: List[Dict[str, Any]] = []
            for _ in range(changes_per_round):
                kind = rng.choice(["insert", "insert", "remove", "addMark"])
                op = (
                    _random_insert(rng, writer, 6)
                    if kind == "insert"
                    else _random_delete(rng, writer)
                    if kind == "remove"
                    else _random_add_mark(rng, writer, [])
                )
                if op is None:
                    continue
                change, _ = writer.change([op])
                batch.append(change)
            session_rounds.append(batch)
        per_session.append(session_rounds)
    return per_session


def time_serve_ab(
    sessions: int = 8,
    rounds: int = 8,
    changes_per_round: int = 8,
    doc_len: int = 200,
    seed: int = 0,
    deadline_ms: float = 25.0,
    batch_target: int = 64,
) -> Dict[str, Any]:
    """Served continuous batching vs naive per-change ingest, same traffic.

    Traffic: ``sessions`` independent editors, each typing its own document
    behind its own replica; per round every session emits
    ``changes_per_round`` changes.  The **naive** leg is today's calling
    convention — one ``apply_changes_with_patches`` launch per change, in
    the same global round-robin arrival order.  The **served** leg submits
    the identical traffic through a :class:`ServePlane` (manual stepping:
    one drain per arrival round — deterministic on the 1-core box) so each
    round collapses into one or two pow2-bucketed cohort launches.

    Both legs run a full compile pass on throwaway state first, so the
    timed comparison is the steady state; compile pressure is reported
    separately as the count of distinct jit-shape keys each leg's timed
    pass touched (the serving plane's ``compile_cache_{hit,miss}`` proxy).
    Per-submission admit-to-applied latencies come from the Submission
    timestamps, so the A/B needs no telemetry and the two legs measure
    identically.  Byte-identity of the two legs' per-session patch streams
    is asserted, not assumed.
    """
    from peritext_tpu.ops import TpuUniverse
    from peritext_tpu.runtime.serve import ServePlane, cohort_shape_key

    rng = random.Random(seed)
    traffic = _serve_author_sessions(sessions, rounds, changes_per_round, doc_len, rng)
    names = [f"r{s}" for s in range(sessions)]
    n_rounds = rounds + 1  # round 0 is the genesis round

    def arrival(round_i: int):
        """Global arrival order within a round: round-robin by session."""
        k = max(len(traffic[s][round_i]) for s in range(sessions))
        for j in range(k):
            for s in range(sessions):
                if j < len(traffic[s][round_i]):
                    yield s, traffic[s][round_i][j]

    total_ops = sum(
        len(c["ops"]) for sr in traffic for batch in sr for c in batch
    )

    # -- naive leg: one launch per change ------------------------------------
    def run_naive():
        uni = TpuUniverse(names)
        lat: List[float] = []
        streams: Dict[str, List[Any]] = {n: [] for n in names}
        shapes = set()
        t0 = time.perf_counter()
        for round_i in range(n_rounds):
            for s, change in arrival(round_i):
                cohort = {names[s]: [change]}
                shapes.add(cohort_shape_key(uni, cohort))
                t1 = time.perf_counter()
                out = uni.apply_changes_with_patches(cohort)
                lat.append(time.perf_counter() - t1)
                streams[names[s]].extend(out[names[s]])
        wall = time.perf_counter() - t0
        return uni, wall, lat, streams, shapes

    # -- served leg: continuous batching per arrival round -------------------
    def run_served():
        uni = TpuUniverse(names)
        plane = ServePlane(
            uni,
            start=False,
            batch_target=batch_target,
            deadline_ms=deadline_ms,
        )
        sess = [
            plane.session(f"s{s}", replica=names[s], record_stream=True)
            for s in range(sessions)
        ]
        max_flush = 0.0
        lat: List[float] = []
        t0 = time.perf_counter()
        for round_i in range(n_rounds):
            subs = [sess[s].submit([change]) for s, change in arrival(round_i)]
            tf = time.perf_counter()
            leftover = plane.drain()
            max_flush = max(max_flush, time.perf_counter() - tf)
            assert leftover == 0, "served leg failed to drain an arrival round"
            lat.extend(sub.t_done - sub.t0 for sub in subs)
        wall = time.perf_counter() - t0
        streams = {names[s]: list(sess[s].patch_log) for s in range(sessions)}
        return uni, plane, wall, lat, streams, max_flush

    # Compile passes (throwaway state), then the timed steady-state passes.
    # The first (cheap, few-launch) served pass also stabilizes the
    # process-wide adaptive span-cap floor: a floor that grows MID-pass
    # leaves later universes starting at a wider cap whose early-round
    # shapes were never compiled — the timed legs must start from the
    # stabilized floor or they pay compiles the throwaways didn't.
    run_served()
    run_naive()
    run_served()
    uni_n, naive_wall, naive_lat, naive_streams, naive_shapes = run_naive()
    uni_s, plane, served_wall, served_lat, served_streams, max_flush = run_served()

    # The hard wall: served batching must be invisible in the streams and
    # in the materialized documents.  (Digests are NOT comparable across
    # universes here: attr intern ids assign in first-seen order, which
    # legitimately differs between per-change and cohort-grouped encoding
    # while decoding identically.)
    assert served_streams == naive_streams, "served/naive patch streams diverged"
    assert uni_s.spans_batch() == uni_n.spans_batch(), "served/naive spans diverged"

    naive_lat.sort()
    served_lat.sort()

    def q(vals, p):
        return vals[min(len(vals) - 1, int(p * len(vals)))] if vals else 0.0

    window_s = deadline_ms / 1000.0 + max_flush
    return {
        "sessions": sessions,
        "rounds": rounds,
        "changes_per_round": changes_per_round,
        "doc_len": doc_len,
        "total_ops": total_ops,
        "deadline_ms": deadline_ms,
        "batch_target": plane._batch_target,
        "naive_ops_per_sec": total_ops / naive_wall,
        "served_ops_per_sec": total_ops / served_wall,
        "served_vs_naive": naive_wall / served_wall,
        "naive_launches": uni_n.stats["launches"],
        "served_launches": uni_s.stats["launches"],
        "naive_p50_s": q(naive_lat, 0.50),
        "naive_p95_s": q(naive_lat, 0.95),
        "served_p50_admit_to_applied_s": q(served_lat, 0.50),
        "served_p95_admit_to_applied_s": q(served_lat, 0.95),
        "batch_window_s": window_s,
        "served_p95_within_window": q(served_lat, 0.95) <= window_s,
        "naive_compiled_shapes": len(naive_shapes),
        "served_compiled_shapes": plane.stats["compile_cache_misses"],
        "serve_stats": dict(plane.stats),
    }


def time_serve_shard_ab(
    sessions: int = 64,
    rounds: int = 4,
    changes_per_round: int = 8,
    doc_len: int = 600,
    seed: int = 0,
    deadline_ms: float = 25.0,
    batch_target: int = 64,
    shard_counts: Any = (1, 8),
) -> Dict[str, Any]:
    """Sharded vs single-shard serving on identical traffic (config 8).

    The single-shard leg is today's ServePlane shape: one scheduler, one
    universe — every cohort launch sweeps the full ``[R, C]`` fleet plane
    even though the batch target only advances ``batch_target`` changes.
    The K-shard legs run the SAME traffic through a
    :class:`ShardedServePlane`: per-shard schedulers flush per-shard
    cohorts whose launches sweep 1/K of the rows for the same budget.

    Protocol (the loaded 1-core box): every leg first runs a full
    throwaway pass so the jit shapes and the process-wide adaptive
    span-cap floor stabilize, then the timed passes run back to back —
    steady-state walls only.  Per-session byte-identity is asserted
    in-harness two ways: (a) every leg's per-session patch streams are
    equal to every other leg's, and (b) each session's accumulated
    stream reconstructs its replica's final spans
    (``accumulate_patches``).  Fleet-wide compiled-shape counts come
    from the union of per-shard shape keys (equal-width shards share
    programs process-wide, so the union is the honest jit-cache
    pressure).
    """
    from peritext_tpu.oracle import accumulate_patches
    from peritext_tpu.runtime.serve_shard import ShardedServePlane

    shard_counts = [int(k) for k in shard_counts]
    rng = random.Random(seed)
    traffic = _serve_author_sessions(sessions, rounds, changes_per_round, doc_len, rng)
    names = [f"r{s}" for s in range(sessions)]
    n_rounds = rounds + 1  # round 0 is the genesis round

    def arrival(round_i: int):
        k = max(len(traffic[s][round_i]) for s in range(sessions))
        for j in range(k):
            for s in range(sessions):
                if j < len(traffic[s][round_i]):
                    yield s, traffic[s][round_i][j]

    total_ops = sum(
        len(c["ops"]) for sr in traffic for batch in sr for c in batch
    )

    def run(shards: int, record: bool):
        plane = ShardedServePlane(
            shards,
            start=False,  # manual stepping: deterministic on the 1-core box
            batch_target=batch_target,
            deadline_ms=deadline_ms,
        )
        sess = [
            plane.session(f"s{s}", replica=names[s], record_stream=record)
            for s in range(sessions)
        ]
        lat: List[float] = []
        t0 = time.perf_counter()
        for round_i in range(n_rounds):
            subs = [sess[s].submit([change]) for s, change in arrival(round_i)]
            leftover = plane.drain()
            assert leftover == 0, "sharded serve leg failed to drain a round"
            lat.extend(sub.t_done - sub.t0 for sub in subs)
        wall = time.perf_counter() - t0
        streams = (
            {names[s]: list(sess[s].patch_log) for s in range(sessions)}
            if record
            else None
        )
        return plane, wall, lat, streams

    # Stabilize compiles + the adaptive span-cap floor for EVERY leg
    # before any timed pass (a floor that grows mid-run re-shapes later
    # legs' early rounds — PROFILE_r06.md protocol).
    for k in shard_counts:
        run(k, record=False)

    legs: List[Dict[str, Any]] = []
    reference_streams = None
    for k in shard_counts:
        plane, wall, lat, streams = run(k, record=True)
        lat.sort()

        def q(vals, p):
            return vals[min(len(vals) - 1, int(p * len(vals)))] if vals else 0.0

        if reference_streams is None:
            reference_streams = streams
            # Reconstruction: each session's stream must rebuild its
            # replica — the in-harness byte-identity anchor (a naive
            # per-change leg at this shape would dominate the runtime;
            # serve-vs-naive identity is pinned at test shape in
            # tests/test_serve_shard.py).
            for name in names:
                rebuilt = accumulate_patches(streams[name])
                assert rebuilt == plane.spans(name), (
                    f"patch stream failed to reconstruct {name}"
                )
        else:
            assert streams == reference_streams, (
                f"{k}-shard leg's patch streams diverged from the "
                f"{shard_counts[0]}-shard leg's"
            )
        stats = plane.stats
        launches = sum(
            s.universe.stats["launches"] for s in plane.shards if s.universe
        )
        legs.append(
            {
                "shards": k,
                "wall_s": wall,
                "ops_per_sec": total_ops / wall,
                "launches": launches,
                "flushes": stats["flushes"],
                "fleet_compiled_shapes": stats["fleet_compiled_shapes"],
                "p50_admit_to_applied_s": q(lat, 0.50),
                "p95_admit_to_applied_s": q(lat, 0.95),
                "deadline_misses": stats["deadline_misses"],
            }
        )

    base = legs[0]
    for leg in legs:
        leg["speedup_vs_first"] = base["wall_s"] / leg["wall_s"]
    return {
        "sessions": sessions,
        "rounds": rounds,
        "changes_per_round": changes_per_round,
        "doc_len": doc_len,
        "total_ops": total_ops,
        "batch_target": batch_target,
        "deadline_ms": deadline_ms,
        "byte_identity": True,  # asserted above; reaching here means it held
        "legs": legs,
        "shape_bound_ok": all(
            leg["fleet_compiled_shapes"] <= 2 * base["fleet_compiled_shapes"]
            for leg in legs
        ),
    }


def time_elastic_ab(
    sessions: int = 16,
    rounds: int = 12,
    changes_per_round: int = 4,
    doc_len: int = 400,
    seed: int = 0,
    deadline_ms: float = 25.0,
    batch_target: int = 64,
    shards: int = 4,
    spread: float = 2.0,
    ticks_per_round: int = 4,
    slo_target_ms: Optional[float] = None,
) -> Dict[str, Any]:
    """Static vs autoscaled sharded serving under a load spike (config 9).

    The spike: every session is pinned to shard 0 of a ``shards``-shard
    fleet, so the hot shard's cohort launches sweep a pow2(sessions)-row
    universe while the other shards idle.  Both legs run IDENTICAL
    traffic (same authored matrix, same arrival order, manual stepping).
    The **static** leg is the control: no controller, the fleet stays
    pinned.  The **elastic** leg ticks an :class:`ElasticController`
    once per round (cooldown 0 — the manual-mode analog of the 1s
    control loop): the pending-spread rule sheds the hot shard's busiest
    session to the coldest shard via live migration, so the hot shard's
    width — and every subsequent launch's row sweep — shrinks round by
    round with no human action.

    The record is per-round p95 admit-to-applied: ``early`` (rounds 1-3,
    spike onset, identical shape in both legs) vs ``late`` (last 3
    rounds — the elastic leg has rebalanced by then, the static leg has
    not).  Per-session byte-identity between the legs is asserted
    in-harness (streams pairwise equal + the static leg's streams
    reconstruct its replicas), so the latency win can't come from
    dropped or reordered work.

    With ``slo_target_ms``, each leg runs with telemetry on and a fresh
    ``e2e.admit_to_applied:p95=<target>`` SLO plan installed: the leg
    dicts then carry the plan's final verdict, and ``recovered``
    additionally requires the elastic leg's late p95 to come back UNDER
    the target while the static control's stays over it.  The harness
    controller runs ``watch_slo=False`` (decisions from load spread
    alone): burn depends on real latencies, so a burn-fed decision
    sequence diverges from its warmup pass and mints jit shapes mid-
    measurement — the burn-split rule is pinned deterministically in
    tests/test_elastic.py instead.
    """
    from peritext_tpu.oracle import accumulate_patches
    from peritext_tpu.runtime import slo as slo_mod
    from peritext_tpu.runtime import telemetry
    from peritext_tpu.runtime.elastic import ElasticController
    from peritext_tpu.runtime.serve_shard import ShardedServePlane

    rng = random.Random(seed)
    traffic = _serve_author_sessions(sessions, rounds, changes_per_round, doc_len, rng)
    names = [f"r{s}" for s in range(sessions)]
    n_rounds = rounds + 1  # round 0 is the genesis round

    def arrival(round_i: int):
        k = max(len(traffic[s][round_i]) for s in range(sessions))
        for j in range(k):
            for s in range(sessions):
                if j < len(traffic[s][round_i]):
                    yield s, traffic[s][round_i][j]

    total_ops = sum(
        len(c["ops"]) for sr in traffic for batch in sr for c in batch
    )

    def run(elastic: bool, record: bool):
        if slo_target_ms is not None:
            telemetry.reset()
            telemetry.enable()
            slo_mod.install(
                f"e2e.admit_to_applied:p95={slo_target_ms},window=128,min=32"
            )
        plane = ShardedServePlane(
            shards,
            start=False,  # manual stepping: deterministic on the 1-core box
            batch_target=batch_target,
            deadline_ms=deadline_ms,
        )
        # The spike: EVERY session pinned to shard 0.
        sess = [
            plane.session(f"s{s}", replica=names[s], shard=0, record_stream=record)
            for s in range(sessions)
        ]
        ctl = (
            ElasticController(
                plane, interval=3600.0, spread=spread, cooldown=0.0,
                watch_slo=False, start=False,
            )
            if elastic
            else None
        )
        round_lat: List[List[float]] = []
        t0 = time.perf_counter()
        for round_i in range(n_rounds):
            # Traffic arrives in ``ticks_per_round`` sub-bursts with a
            # control tick after each one — the manual-mode stand-in for
            # a control interval shorter than a traffic round.  Each tick
            # observes the standing backlog and migrates at most one
            # session (a migration's own source drain consumes the
            # backlog, so the next burst must rebuild the pressure).
            # Migration time lands in THIS round's admit-to-applied
            # latencies — the recovery claim is that late-round p95
            # comes back down anyway.
            arrivals = list(arrival(round_i))
            bursts = max(1, ticks_per_round)
            per = (len(arrivals) + bursts - 1) // bursts
            subs = []
            for b in range(bursts):
                chunk = arrivals[b * per:(b + 1) * per]
                subs.extend(sess[s].submit([change]) for s, change in chunk)
                if ctl is not None and round_i > 0 and chunk:
                    ctl.tick()
            leftover = plane.drain()
            assert leftover == 0, "elastic A/B leg failed to drain a round"
            round_lat.append(sorted(sub.t_done - sub.t0 for sub in subs))
        wall = time.perf_counter() - t0
        streams = (
            {names[s]: list(sess[s].patch_log) for s in range(sessions)}
            if record
            else None
        )
        dist = [len(sh.real) for sh in plane.shards]
        ctl_stats = dict(ctl.stats) if ctl is not None else None
        slo_verdict = None
        if slo_target_ms is not None:
            summary = slo_mod.summary()
            if summary:
                (_, slo_verdict), = summary.items()
            slo_mod.reset()
        return plane, wall, round_lat, streams, dist, ctl_stats, slo_verdict

    def q95(vals: List[float]) -> float:
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(0.95 * len(vals)))] if vals else 0.0

    # Stabilize every leg's jit shapes + the adaptive span-cap floor
    # before any timed pass (the elastic leg mints narrower post-migration
    # universe shapes the static leg never sees).
    run(elastic=False, record=False)
    run(elastic=True, record=False)

    legs: List[Dict[str, Any]] = []
    reference_streams = None
    for elastic in (False, True):
        plane, wall, round_lat, streams, dist, ctl_stats, slo_verdict = run(
            elastic=elastic, record=True
        )
        if reference_streams is None:
            reference_streams = streams
            for name in names:
                rebuilt = accumulate_patches(streams[name])
                assert rebuilt == plane.spans(name), (
                    f"patch stream failed to reconstruct {name}"
                )
        else:
            assert streams == reference_streams, (
                "elastic leg's patch streams diverged from the static leg's"
            )
        early = [v for r in round_lat[1:4] for v in r]
        late = [v for r in round_lat[-3:] for v in r]
        leg = {
            "leg": "elastic" if elastic else "static",
            "wall_s": wall,
            "ops_per_sec": total_ops / wall,
            "early_p95_s": q95(early),
            "late_p95_s": q95(late),
            "round_p95_ms": [round(q95(r) * 1000, 2) for r in round_lat],
            "shard_sessions": dist,
        }
        if ctl_stats is not None:
            leg["controller"] = ctl_stats
        if slo_verdict is not None:
            leg["slo"] = slo_verdict
        legs.append(leg)

    static, elastic_leg = legs
    return {
        "sessions": sessions,
        "rounds": rounds,
        "changes_per_round": changes_per_round,
        "doc_len": doc_len,
        "total_ops": total_ops,
        "batch_target": batch_target,
        "deadline_ms": deadline_ms,
        "shards": shards,
        "spread": spread,
        "ticks_per_round": ticks_per_round,
        "slo_target_ms": slo_target_ms,
        "byte_identity": True,  # asserted above; reaching here means it held
        "legs": legs,
        # Autonomous recovery: the elastic leg's post-rebalance p95 beat
        # the static control's AND its own spike-onset p95; at least one
        # migration actually happened (the win must come from rebalancing,
        # not noise).
        "recovered": (
            elastic_leg["late_p95_s"] < static["late_p95_s"]
            and elastic_leg["late_p95_s"] < elastic_leg["early_p95_s"]
            and (elastic_leg.get("controller") or {}).get("migrations", 0) >= 1
            and (
                slo_target_ms is None
                or elastic_leg["late_p95_s"] * 1000 <= slo_target_ms
                < static["late_p95_s"] * 1000
            )
        ),
    }


def time_lifecycle_ab(
    sessions: int = 32,
    rounds: int = 10,
    changes_per_round: int = 16,
    doc_len: int = 120,
    seed: int = 0,
    deadline_ms: float = 25.0,
    batch_target: int = 64,
    shards: int = 2,
    watermark: int = 4,
    zipf_s: float = 1.1,
    slo_cold_target_ms: Optional[float] = None,
) -> Dict[str, Any]:
    """Multi-tenant document lifecycle vs resident-only control (config 10).

    The shape: ``sessions`` independent documents behind a
    ``shards``-shard plane, with a Zipf(``zipf_s``) access schedule —
    session 0 is the hottest tenant, the tail is touched rarely (the
    many-docs-few-hot serving reality the lifecycle exists for).  Both
    legs run IDENTICAL traffic: the schedule and every change are
    authored once, and each leg replays them in the same arrival order.

    The **control** leg keeps every document resident — the device fleet
    must hold pow2(``sessions``/shard) rows per shard forever.  The
    **lifecycle** leg runs a :class:`DocLifecycle` with a
    ``watermark``-doc resident bound: admission pressure LRU-evicts past
    the watermark (durable checkpoint + device row freed), cold
    documents hydrate transparently on their next submit, and a policy
    tick runs once per round.  The record is the **tenancy ratio**
    (documents served / peak device rows held — the control's is ~1x,
    the lifecycle leg's must be ≥ sessions/watermark-ish) and the
    **warm/cold latency split**: per-submission admit-to-applied
    classified by ``Submission.lat_class``, plus the
    ``e2e.admit_to_applied_{warm,cold}`` histogram counts proving the
    split is SLO-addressable (with ``slo_cold_target_ms`` a live
    ``e2e.admit_to_applied_cold:p95`` objective runs over the lifecycle
    leg and its verdict is stamped).

    Byte-identity is asserted in-harness: the two legs' per-session
    patch streams are pairwise equal, the control streams reconstruct
    their replicas (``accumulate_patches``), and after a final warm-all
    pass the lifecycle leg's spans equal the control's — the tenancy win
    can't come from dropped or reordered work.
    """
    from peritext_tpu.oracle import accumulate_patches
    from peritext_tpu.runtime import slo as slo_mod
    from peritext_tpu.runtime import telemetry
    from peritext_tpu.runtime.lifecycle import DocLifecycle
    from peritext_tpu.runtime.serve_shard import ShardedServePlane

    rng = random.Random(seed)
    names = [f"lc{s}" for s in range(sessions)]
    # Zipf access schedule: session s has rank s, p(s) ∝ 1/(s+1)^zipf_s.
    # Drawn ONCE — both legs replay the identical arrival sequence.
    weights = [1.0 / (s + 1) ** zipf_s for s in range(sessions)]
    schedule = [
        rng.choices(range(sessions), weights=weights, k=changes_per_round)
        for _ in range(rounds)
    ]
    needed = [0] * sessions
    for burst in schedule:
        for s in burst:
            needed[s] += 1

    # Author each session's causal lineage once (genesis + exactly the
    # changes its schedule slots consume).
    lineage: List[List[Dict[str, Any]]] = []
    for s in range(sessions):
        writer = Doc(names[s])
        text = "".join(
            rng.choice("abcdefghijklmnopqrstuvwxyz ") for _ in range(doc_len)
        )
        genesis, _ = writer.change(
            [
                {"path": [], "action": "makeList", "key": "text"},
                {"path": ["text"], "action": "insert", "index": 0, "values": list(text)},
            ]
        )
        chain = [genesis]
        while len(chain) < needed[s] + 1:
            kind = rng.choice(["insert", "insert", "remove", "addMark"])
            op = (
                _random_insert(rng, writer, 6)
                if kind == "insert"
                else _random_delete(rng, writer)
                if kind == "remove"
                else _random_add_mark(rng, writer, [])
            )
            if op is None:
                continue
            change, _ = writer.change([op])
            chain.append(change)
        lineage.append(chain)

    total_changes = sessions + sum(needed)

    def run(lifecycle: bool, record: bool):
        telemetry.reset()
        telemetry.enable()
        if lifecycle and slo_cold_target_ms is not None:
            slo_mod.install(
                f"e2e.admit_to_applied_cold:p95={slo_cold_target_ms},"
                "window=64,min=8"
            )
        plane = ShardedServePlane(
            shards,
            start=False,  # manual stepping: deterministic on the 1-core box
            batch_target=batch_target,
            deadline_ms=deadline_ms,
        )
        lc = (
            DocLifecycle(plane, start=False, watermark=watermark, cooldown=0.0)
            if lifecycle
            else None
        )
        sess = [
            plane.session(f"s{s}", replica=names[s], record_stream=record)
            for s in range(sessions)
        ]

        def rows() -> int:
            with plane._lock:
                return sum(
                    len(sh.universe.replica_ids)
                    for sh in plane.shards
                    if sh.universe is not None
                )

        peak_rows = rows()
        subs = []
        cursor = [1] * sessions
        t0 = time.perf_counter()
        # Genesis round: every document comes into existence once — in the
        # lifecycle leg most sessions are already cold (admission pressure
        # evicted them at creation), so this round IS the cold-start storm.
        for s in range(sessions):
            subs.append(sess[s].submit([lineage[s][0]]))
        leftover = plane.drain()
        assert leftover == 0, "lifecycle A/B genesis round failed to drain"
        peak_rows = max(peak_rows, rows())
        for burst in schedule:
            for s in burst:
                subs.append(sess[s].submit([lineage[s][cursor[s]]]))
                cursor[s] += 1
            leftover = plane.drain()
            assert leftover == 0, "lifecycle A/B round failed to drain"
            if lc is not None:
                lc.tick()
            peak_rows = max(peak_rows, rows())
        wall = time.perf_counter() - t0
        streams = (
            {names[s]: list(sess[s].patch_log) for s in range(sessions)}
            if record
            else None
        )
        # Spans are only readable resident, and under the watermark a
        # fleet-wide warm-all is self-defeating (each hydration
        # pressure-evicts an earlier one) — hydrate and read one session
        # at a time: pressure excludes the hydration target, so it is
        # guaranteed resident for its own read.  Identity therefore holds
        # through the LAST eviction of every session too.
        spans = None
        if record:
            spans = {}
            for s in range(sessions):
                if lc is not None and sess[s]._cold:
                    lc.hydrate(f"s{s}")
                spans[names[s]] = plane.spans(names[s])
        # Unclassified submissions (no lifecycle attached) are warm: the
        # control leg never pays a cold start.
        warm = sorted(
            sub.t_done - sub.t0
            for sub in subs
            if (getattr(sub, "lat_class", None) or "warm") == "warm"
        )
        cold = sorted(
            sub.t_done - sub.t0
            for sub in subs
            if getattr(sub, "lat_class", None) == "cold"
        )
        hist = telemetry.snapshot()["histograms"]
        hist_counts = {
            k: hist[k]["count"]
            for k in ("e2e.admit_to_applied_warm", "e2e.admit_to_applied_cold")
            if k in hist
        }
        lc_stats = dict(lc.stats) if lc is not None else None
        slo_verdict = None
        if lifecycle and slo_cold_target_ms is not None:
            summary = slo_mod.summary()
            if summary:
                (_, slo_verdict), = summary.items()
            slo_mod.reset()
        plane.close()
        telemetry.reset()
        return wall, streams, spans, warm, cold, peak_rows, hist_counts, lc_stats, slo_verdict

    def q95(vals: List[float]) -> float:
        return vals[min(len(vals) - 1, int(0.95 * len(vals)))] if vals else 0.0

    # Stabilize each leg's jit shapes (the legs mint different pow2
    # universe widths) + the adaptive span-cap floor before timing.
    run(lifecycle=False, record=False)
    run(lifecycle=True, record=False)

    legs: List[Dict[str, Any]] = []
    reference = None
    for lifecycle in (False, True):
        wall, streams, spans, warm, cold, peak_rows, hist_counts, lc_stats, slo_verdict = run(
            lifecycle=lifecycle, record=True
        )
        if reference is None:
            reference = (streams, spans)
            for name in names:
                rebuilt = accumulate_patches(streams[name])
                assert rebuilt == spans[name], (
                    f"control patch stream failed to reconstruct {name}"
                )
        else:
            assert streams == reference[0], (
                "lifecycle leg's patch streams diverged from the control's"
            )
            assert spans == reference[1], (
                "lifecycle leg's spans diverged from the control's"
            )
        leg = {
            "leg": "lifecycle" if lifecycle else "control",
            "wall_s": wall,
            "changes_per_sec": total_changes / wall,
            "peak_device_rows": peak_rows,
            "tenancy_ratio": round(sessions / peak_rows, 3) if peak_rows else None,
            "warm_p95_ms": round(q95(warm) * 1000, 3),
            "cold_p95_ms": round(q95(cold) * 1000, 3) if cold else None,
            "warm_count": len(warm),
            "cold_count": len(cold),
            "latency_histograms": hist_counts,
        }
        if lc_stats is not None:
            leg["lifecycle_stats"] = lc_stats
        if slo_verdict is not None:
            leg["slo_cold"] = slo_verdict
        legs.append(leg)

    control, lifecycle_leg = legs
    return {
        "sessions": sessions,
        "rounds": rounds,
        "changes_per_round": changes_per_round,
        "doc_len": doc_len,
        "total_changes": total_changes,
        "batch_target": batch_target,
        "deadline_ms": deadline_ms,
        "shards": shards,
        "watermark": watermark,
        "zipf_s": zipf_s,
        "slo_cold_target_ms": slo_cold_target_ms,
        "byte_identity": True,  # asserted above; reaching here means it held
        "legs": legs,
        # The headline stamps the driver contract reads.
        "tenancy_ratio": lifecycle_leg["tenancy_ratio"],
        "cold_start_p95_ms": lifecycle_leg["cold_p95_ms"],
        "warm_p95_ms": lifecycle_leg["warm_p95_ms"],
        # Bounded fleet actually over-subscribed, cold starts actually
        # measured AND SLO-visible (both split histograms populated).
        "ok": (
            (lifecycle_leg["tenancy_ratio"] or 0) >= 4.0
            and lifecycle_leg["cold_count"] > 0
            and lifecycle_leg["latency_histograms"].get(
                "e2e.admit_to_applied_cold", 0
            ) > 0
            and lifecycle_leg["latency_histograms"].get(
                "e2e.admit_to_applied_warm", 0
            ) > 0
        ),
    }


def time_window_single_op(
    doc_len: int = 10_000,
    trials: int = 24,
    seed: int = 0,
    windowed: bool = True,
    patched: bool = True,
    mark_every: int = 4,
) -> Dict[str, Any]:
    """Single-op merge latency on a big document through the full universe
    API — the frontier-bounded window merge's headline shape (ISSUE 12).

    One writer edits a ``doc_len``-char document one small change at a
    time (1-3 typed chars; every ``mark_every``-th trial a short addMark,
    every 5th a delete); each ingest is timed individually with its own
    completion barrier.  ``windowed`` pins PERITEXT_MERGE_WINDOW for the
    leg; identical edit streams per seed either way, so the A/B compares
    the same work, and the returned digest + patch count let the harness
    assert byte-identity across legs.
    """
    from peritext_tpu.ops import TpuUniverse
    from peritext_tpu.oracle import Doc
    from peritext_tpu.testing import window_env

    rng = random.Random(seed)
    d = Doc("writer")
    body = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ") for _ in range(doc_len))
    genesis, _ = d.change([
        {"path": [], "action": "makeList", "key": "text"},
        {"path": ["text"], "action": "insert", "index": 0, "values": list(body)},
    ])
    edits = []
    length = doc_len
    for t in range(trials + 1):  # +1 warmup edit
        if t % 5 == 4:
            op = {"path": ["text"], "action": "delete",
                  "index": rng.randrange(length - 1), "count": 1}
            length -= 1
        elif t % mark_every == mark_every - 1:
            start = rng.randrange(length - 8)
            op = {"path": ["text"], "action": "addMark", "startIndex": start,
                  "endIndex": start + rng.randrange(2, 8),
                  "markType": rng.choice(["strong", "em"])}
        else:
            values = [rng.choice("abcdefghijklmnopqrstuvwxyz ")
                      for _ in range(rng.randrange(1, 4))]
            op = {"path": ["text"], "action": "insert",
                  "index": rng.randrange(length), "values": values}
            length += len(values)
        change, _ = d.change([op])
        edits.append(change)

    capacity = 1
    while capacity < doc_len + 8 * (trials + 2) + 64:
        capacity *= 2

    with window_env(windowed):
        # Compile pass: a throwaway universe walks the WHOLE edit sequence
        # first (distinct maxk / has-marks combinations each mint a
        # program), so the timed pass below measures launches, not
        # compiles — the time_patched_fleet idiom.
        throwaway = TpuUniverse(["r1"], capacity=capacity, max_mark_ops=128)
        throwaway.apply_changes_with_patches({"r1": [genesis]})
        warm_ingest = (
            throwaway.apply_changes_with_patches
            if patched
            else throwaway.apply_changes
        )
        for change in edits:
            warm_ingest({"r1": [change]})
        np.asarray(throwaway.states.length)

        uni = TpuUniverse(["r1"], capacity=capacity, max_mark_ops=128)
        ingest = (
            uni.apply_changes_with_patches if patched else uni.apply_changes
        )
        patch_count = 0

        def run(change):
            nonlocal patch_count
            out = ingest({"r1": [change]})
            if patched:
                patch_count += len(out["r1"])
            np.asarray(uni.states.length)  # completion barrier
            return out

        run(genesis)
        run(edits[0])  # warm the universe's own caches (mirror, spans)
        latencies = []
        for change in edits[1:]:
            start = time.perf_counter()
            run(change)
            latencies.append(time.perf_counter() - start)
        digest = int(uni.digests()[0])
        stats = dict(uni.stats)
    latencies.sort()
    return {
        "doc_len": doc_len,
        "trials": trials,
        "windowed": windowed,
        "patched": patched,
        "p50_ms": round(1e3 * latencies[len(latencies) // 2], 3),
        "mean_ms": round(1e3 * sum(latencies) / len(latencies), 3),
        "min_ms": round(1e3 * latencies[0], 3),
        "digest": digest,
        "patch_count": patch_count,
        "windowed_launches": stats.get("windowed_launches", 0),
        "window_fallbacks": stats.get("window_fallbacks", 0),
        "launches": stats.get("launches", 0),
    }
