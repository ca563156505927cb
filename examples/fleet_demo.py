#!/usr/bin/env python3
"""Fleet demo: the north-star workload through the public batched API.

Hundreds-to-thousands of document replicas resident on device as one
TpuUniverse, ingesting concurrent edit streams in a single launch per round,
convergence-checked with one batched digest computation (BASELINE.json
configs 3-5 shape).  FLEET_REPLICAS / FLEET_ROUNDS env vars scale it up;
it runs on whatever platform JAX_PLATFORMS selects.
"""
import os
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# The host phases of TpuUniverse.apply_changes before its launch.
HOST_SPANS = ("ingest.prepare", "ingest.stage", "ingest.window_census", "ingest.put")


def host_seconds(snapshot: dict) -> float:
    hists = snapshot["histograms"]
    return sum(hists.get(f"span.{name}.seconds", {}).get("sum", 0.0) for name in HOST_SPANS)


def main() -> None:
    replicas = int(os.environ.get("FLEET_REPLICAS", "256"))
    rounds = int(os.environ.get("FLEET_ROUNDS", "3"))
    from peritext_tpu import compile_cache
    from peritext_tpu.bench.workloads import make_merge_workload
    from peritext_tpu.ops import TpuUniverse
    from peritext_tpu.runtime import telemetry

    compile_cache.enable()
    # The host phases before each launch are telemetry spans; the demo
    # reads their histograms for its host/device split.
    telemetry.enable()
    # Four distinct writer streams over a shared 400-char genesis document.
    workload = make_merge_workload(doc_len=400, ops_per_merge=48, num_streams=4, seed=7)
    streams = workload["streams"]
    names = [f"replica-{i:05d}" for i in range(replicas)]
    uni = TpuUniverse(names, capacity=1024, max_mark_ops=256)

    t0 = time.perf_counter()
    uni.apply_changes({name: [workload["genesis"]] for name in names})
    print(f"genesis: {replicas} replicas bootstrapped in {time.perf_counter()-t0:.2f}s")

    # With more than one device the fleet lays out over a (replica, seq)
    # mesh — run with XLA_FLAGS=--xla_force_host_platform_device_count=8
    # (or on a real slice) to see the sharded path.
    import jax

    n_dev = len(jax.devices())
    if n_dev > 1 and replicas % n_dev == 0:
        from peritext_tpu.parallel import make_mesh

        mesh = make_mesh(jax.devices(), n_dev, 1)
        uni.shard(mesh, shard_seq=False)
        print(f"fleet sharded over mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    total_ops = 0
    wall = 0.0
    # The host/device split reports the measured rounds only, so exclude
    # the genesis bootstrap's host share recorded above.
    host_before_rounds = host_seconds(telemetry.snapshot())
    for rnd in range(rounds):
        # Each replica merges one writer stream per round, round-robin — so
        # after every round, replicas on the same stream schedule must agree.
        batch = {}
        for i, name in enumerate(names):
            stream = streams[(i + rnd) % len(streams)]
            batch[name] = stream
            total_ops += sum(len(c["ops"]) for c in stream)
        t0 = time.perf_counter()
        uni.apply_changes(batch)
        # Host readback barrier: JAX dispatch is async, so round wall time
        # without a barrier would only measure enqueueing.
        np.asarray(uni.states.length)
        dt = time.perf_counter() - t0
        print(f"round {rnd}: merged {len(streams)} streams across {replicas} replicas in {dt:.2f}s")
        wall += dt

    # After `rounds` round-robin rounds every replica has seen streams
    # {(i+r) % 4}, so replicas with i % 4 equal share identical histories.
    digests = uni.digests()
    groups = Counter()
    for i, digest in enumerate(digests):
        groups[(i % len(streams), int(digest))] += 1
    schedules = {}
    for (schedule, digest), count in groups.items():
        schedules.setdefault(schedule, set()).add(digest)
    for schedule, unique in sorted(schedules.items()):
        status = "CONVERGED" if len(unique) == 1 else f"DIVERGED ({len(unique)} states)"
        print(f"schedule class {schedule}: {status}")
    assert all(len(u) == 1 for u in schedules.values()), "fleet diverged!"

    spans = uni.spans(names[0])
    text = "".join(s["text"] for s in spans)
    marked = sum(1 for s in spans if s["marks"])
    host_s = host_seconds(telemetry.snapshot()) - host_before_rounds
    # Device share = barriered round wall time minus the host phases
    # before each launch.
    dev_s = max(wall - host_s, 0.0)
    print(
        f"\nfleet consistent: {replicas} replicas, {total_ops} ops merged; "
        f"replica-0: {len(text)} chars in {len(spans)} spans ({marked} marked)\n"
        f"time split: host {host_s:.3f}s, device {dev_s:.3f}s of {wall:.3f}s barriered "
        f"({'host-bound' if host_s > dev_s else 'device-bound'})"
    )


if __name__ == "__main__":
    main()
