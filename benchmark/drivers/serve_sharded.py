"""Closed-loop editing through a ``ShardedServePlane``: one shard a chip.

Set-up opens one session per doc (``open_sessions``, placement ``rr``:
doc i on shard i mod the shard count, one shard a chip), loads every doc's
genesis into its shard with ``apply_changes`` (no patches; the shards
side by side), compiles every cohort program on every chip
(``ShardedServePlane.warm``), then sends every writer's first change and
waits for all of them.  The closed loop then runs ``LEADIN_S`` into the
window of ``seconds``: each writer sends its next change the moment the
previous one is answered, so exactly one change of each writer is in
flight.  A client thread resubmits after each shard flush.

``merge_ops_per_s``: the wire ops (``len(change["ops"])``, as
``drivers/fleet.py`` counts them) of the submissions answered inside the
window (``Submission.t_done``), over the window's seconds.  A shard flush
is this cell's round (``units``: ``rounds`` and ``flushes`` alike), so the
per-round readers of the ingest phases and the merge kernels read per
flush here.

The notes say how far the writers got into their scripts: the most
changes one writer sent, against the fewest of a script's first changes
that fit its doc's capacity (tombstones keep their slots); a writer past
that point (``writers_past_capacity``) grows its shard's table.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

import check
from common import freeze_heap, hist_delta, note

LEADIN_S = 6.0
SWEEP_S = 0.002
# A first run in a checkout compiles while it loads and warms.
SETUP_WAIT_S = 1800.0


class Writers(threading.Thread):
    """One writer per doc, each reading its script in order with exactly
    one change in flight.  The thread waits for a shard's flush count to
    move, then answers that shard's writers: each resolved submission is
    recorded and the writer's next change goes out."""

    def __init__(self, plane: Any, sessions: List[Any], scripts: List[Any],
                 shard_of: List[int]) -> None:
        super().__init__(name="bench-writers", daemon=True)
        self._plane = plane
        self._sessions = sessions
        self._scripts = scripts
        self._by_shard: Dict[int, List[int]] = {}
        for d, k in enumerate(shard_of):
            self._by_shard.setdefault(k, []).append(d)
        self.next = [0] * len(sessions)  # index of each writer's next change
        self.flight: Dict[int, Any] = {}  # doc -> its submission in flight
        self.subs: List[List[Any]] = [[] for _ in sessions]
        self.ran_out = 0
        self.refused = 0
        self._seen = [0] * len(plane.shards)
        self._halt = threading.Event()
        self.sending = True

    def send(self, d: int) -> None:
        i = self.next[d]
        if i >= len(self._scripts[d]):
            self.ran_out += 1
            return
        try:
            sub = self._sessions[d].submit([self._scripts[d].get(i)])
        except Exception as exc:  # refused at admission: a failed request
            self.refused += 1
            note(phase="submit_refused", doc=d, index=i, error=repr(exc))
            return
        self.next[d] = i + 1
        self.subs[d].append(sub)
        self.flight[d] = sub

    def outstanding(self) -> int:
        return sum(not s.done() for s in list(self.flight.values()))

    def run(self) -> None:
        while not self._halt.wait(SWEEP_S):
            for k, shard in enumerate(self._plane.shards):
                flushes = shard.plane.stats["flushes"] + shard.plane.stats["flush_failures"]
                if flushes == self._seen[k]:
                    continue
                self._seen[k] = flushes
                for d in self._by_shard.get(k, ()):
                    sub = self.flight.get(d)
                    if sub is None or not sub.done():
                        continue
                    del self.flight[d]
                    if self.sending:
                        self.send(d)

    def stop(self) -> None:
        self._halt.set()
        self.join()


def answer(sub: Any) -> Optional[List[Any]]:
    """A resolved submission's patches; None when it never resolved or
    resolved with an error."""
    if not sub.done():
        return None
    try:
        return sub.result(timeout=0)
    except Exception:
        return None


def settle(writers: Writers, deadline: float) -> None:
    while writers.outstanding() and time.perf_counter() < deadline:
        time.sleep(0.01)


def run(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int, seconds: float,
        window: Any, devices: List[Any]) -> Dict[str, Any]:
    import jax

    from peritext_tpu.runtime.serve_shard import ShardedServePlane

    for need in ("warm", "open_sessions"):
        if not hasattr(ShardedServePlane, need):
            raise RuntimeError(f"this program's ShardedServePlane has no {need}(): "
                               "it cannot compile its cohort programs before traffic")
    n_docs, shards = cfg["docs"], cfg["shards"]
    if len(devices) != shards:
        raise ValueError(f"{shards} shards need {shards} chips, the cell gives {len(devices)}")
    t_setup = time.perf_counter()
    plane = ShardedServePlane(shards=shards, devices=devices, capacity=cfg["capacity"],
                              max_mark_ops=cfg["max_mark_ops"], **cfg["plane"])
    names = [f"d{i}" for i in range(n_docs)]
    sessions = plane.open_sessions([(f"s{i}", names[i]) for i in range(n_docs)])
    shard_of = [s.shard for s in sessions]
    held = []
    for shard in plane.shards:
        leaves = {dev for leaf in jax.tree.leaves(shard.universe.states)
                  for dev in leaf.devices()}
        held.append(sorted(str(dev) for dev in leaves))
    if any(len(h) != 1 for h in held) or len({h[0] for h in held}) != shards:
        raise RuntimeError(f"shards are not one a chip: {held}")

    # Geneses, the shards side by side (their launches are separate
    # programs on separate chips).
    per_shard: Dict[int, Dict[str, List[Any]]] = {}
    for d in range(n_docs):
        per_shard.setdefault(shard_of[d], {})[names[d]] = [traffic["geneses"][d]]
    errors: List[BaseException] = []

    def load(k: int) -> None:
        try:
            uni = plane.shards[k].universe
            uni.apply_changes(per_shard[k])
            jax.block_until_ready(uni.states)
        except BaseException as exc:
            errors.append(exc)

    t = time.perf_counter()
    loaders = [threading.Thread(target=load, args=(k,)) for k in per_shard]
    for th in loaders:
        th.start()
    for th in loaders:
        th.join()
    if errors:
        raise errors[0]
    load_s = time.perf_counter() - t
    t = time.perf_counter()
    warmed = plane.warm()
    warm_s = time.perf_counter() - t

    writers = Writers(plane, sessions, traffic["scripts"], shard_of)
    t = time.perf_counter()
    for d in range(n_docs):
        writers.send(d)
    settle(writers, time.perf_counter() + SETUP_WAIT_S)
    first_s = time.perf_counter() - t
    note(phase="setup", load_s=load_s, warm_s=warm_s, warmed_programs=warmed,
         first_changes_s=first_s, shard_devices=held,
         unresolved_after_first=writers.outstanding(),
         cold_shapes_in_setup=plane.stats["cold_shapes"])
    freeze_heap()

    writers.flight = {}  # the first changes are all answered
    for d in range(n_docs):
        writers.send(d)
    writers.start()
    time.sleep(LEADIN_S)
    stats0 = plane.stats
    window.open()
    t0 = time.perf_counter()
    with window.annotate("bench.window"):
        time.sleep(seconds)
    t1 = time.perf_counter()
    window.close()
    writers.sending = False
    stats1 = plane.stats
    settle(writers, t1 + cfg["result_grace_s"])
    writers.stop()
    unanswered = writers.outstanding()
    plane_stats = plane.stats
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use") for dev in devices]
    # Traced runs only: the windows' mean width, in percent of the table.
    n_win, win_pct = hist_delta(window.telemetry, "ingest.window_frac")
    # ... and the shard launches in flight at each launch, this one counted.
    n_fl, in_flight = hist_delta(window.telemetry, "serve.launches_in_flight")

    ops = 0
    answered = 0
    for d in range(n_docs):
        for i, sub in enumerate(writers.subs[d]):
            if sub.t_done is not None and t0 <= sub.t_done <= t1:
                ops += len(traffic["scripts"][d].get(i)["ops"])
                answered += 1

    def per_shard_delta(key: str, a: Dict[str, Any], b: Dict[str, Any]) -> List[int]:
        return [y.get(key, 0) - x.get(key, 0) for x, y in zip(a["shards"], b["shards"])]

    sample = check.sample(seed, 7, n_docs, cfg["sampled_docs"], strata=shards)
    docs: Dict[int, Dict[str, Any]] = {}
    for d in sample:
        docs[d] = {"genesis": traffic["geneses"][d], "spans": plane.spans(names[d]),
                   "subs": [(traffic["scripts"][d].get(i), answer(sub))
                            for i, sub in enumerate(writers.subs[d])]}
    sent = sum(len(s) for s in writers.subs)
    most = max(len(s) for s in writers.subs)
    past = sum(len(s) > f for s, f in zip(writers.subs, traffic["fits"]))
    ran_out, refused = writers.ran_out, writers.refused
    plane.close()
    plane = sessions = writers = None
    gc.collect()

    return {
        "metrics": {"merge_ops_per_s": ops / (t1 - t0)},
        "attempted": sent,
        "failed": unanswered + refused,
        "memory_peak_bytes": max((p for p in peaks if p is not None), default=None),
        "units": {"flushes": stats1["flushes"] - stats0["flushes"],
                  "rounds": stats1["flushes"] - stats0["flushes"]},
        "trace_spans": "bench.window",
        "notes": {"submissions": sent, "answered_in_window": answered,
                  "window_ops": ops, "unanswered": unanswered, "refused": refused,
                  "writers_out_of_script": ran_out,
                  "most_changes_sent": most,
                  "changes_within_capacity": min(traffic["fits"]),
                  "writers_past_capacity": past,
                  "cold_shapes": plane_stats["cold_shapes"],
                  "cold_shapes_in_window": stats1["cold_shapes"] - stats0["cold_shapes"],
                  "flushes_per_shard": per_shard_delta("flushes", stats0, stats1),
                  "windowed_flushes_per_shard": per_shard_delta("windowed_flushes",
                                                                stats0, stats1),
                  "window_pct_mean": win_pct / n_win if n_win else None,
                  "launches_in_flight_mean": in_flight / n_fl if n_fl else None,
                  "peak_bytes_per_chip": peaks, "setup_to_window_s": t0 - t_setup,
                  "plane": {k: v for k, v in plane_stats.items() if k != "shards"}},
        "compare": lambda: {**check.serve_compare(check.reference(), docs),
                            "unanswered": {"value": unanswered, "limit": 0}},
    }
