"""Frontier-bounded window-merge differentials (ISSUE 12 tentpole).

The windowed path (host census -> gather [R, w_cap] -> merge -> scatter)
must be indistinguishable from the full-table merge at the byte level:
device planes, assembled patch streams, spans, digests, and the persisted
winner cache.  Every test here runs the same delivery twice — windowed
(PERITEXT_MERGE_WINDOW=1 with the engagement floor lowered) and pinned
full-table (PERITEXT_MERGE_WINDOW=0) — and compares everything a client
can observe, asserting the windowed leg actually ENGAGED (a dormant
window path would pass the differentials vacuously).
"""
import random
from contextlib import contextmanager

import numpy as np
import pytest

from peritext_tpu.fuzz import (
    _random_add_mark,
    _random_delete,
    _random_insert,
    _random_remove_mark,
)
from peritext_tpu.ops import TpuUniverse
from peritext_tpu.oracle import Doc
from peritext_tpu.testing import generate_docs, window_env as _window_env

STATE_FIELDS = (
    "elem_ctr", "elem_act", "deleted", "chars", "bnd_def", "bnd_mask",
    "mark_ctr", "mark_act", "mark_action", "mark_type", "mark_attr",
    "length", "mark_count",
)


@contextmanager
def window_env(on: bool, min_cap: str = "64"):
    """Pin the windowed-merge knobs for one leg (ambient-CI-proof)."""
    with _window_env(on, min_cap=min_cap):
        yield


def _drive(batches, windowed, replicas=("r1", "r2"), plain=False, **uni_kw):
    """Ingest a list of per-step change batches; returns (uni, outputs)."""
    uni_kw.setdefault("capacity", 1024)
    uni_kw.setdefault("max_mark_ops", 64)
    with window_env(windowed):
        uni = TpuUniverse(list(replicas), **uni_kw)
        outs = []
        for batch in batches:
            per = {r: batch for r in replicas}
            if plain:
                outs.append(uni.apply_changes(per))
            else:
                outs.append(uni.apply_changes_with_patches(per))
        spans = uni.spans_batch()
        texts = uni.texts()
        digests = uni.digests()
    return uni, outs, spans, texts, digests


def _assert_identical(batches, replicas=("r1", "r2"), plain=False,
                      expect_windowed=True, **uni_kw):
    uw, ow, sw, tw, dw = _drive(batches, True, replicas, plain, **uni_kw)
    uf, of, sf, tf, df = _drive(batches, False, replicas, plain, **uni_kw)
    if expect_windowed:
        assert uw.stats.get("windowed_launches", 0) >= 1, (
            f"windowed path never engaged: {uw.stats}"
        )
    assert uf.stats.get("windowed_launches", 0) == 0
    assert ow == of, "patch streams diverged"
    assert tw == tf
    assert sw == sf
    assert (dw == df).all()
    for f in STATE_FIELDS:
        a = np.asarray(getattr(uw.states, f))
        b = np.asarray(getattr(uf.states, f))
        assert (a == b).all(), f"device plane {f} diverged"
    if uw._wcaches is not None and uf._wcaches is not None:
        assert (np.asarray(uw._wcaches) == np.asarray(uf._wcaches)).all(), (
            "winner cache diverged"
        )
    return uw, uf


def _genesis(n_chars=420, text="windowed merge! "):
    d = Doc("alice")
    body = (text * (n_chars // len(text) + 1))[:n_chars]
    genesis, _ = d.change([
        {"path": [], "action": "makeList", "key": "text"},
        {"path": ["text"], "action": "insert", "index": 0, "values": list(body)},
    ])
    return d, genesis


def _random_stream(seed, steps=10, writers=3, n_chars=420):
    """Multi-writer random edit stream: per-step change batches, fully
    synced between steps (each step's batch is concurrent edits from up to
    ``writers`` actors at independent random positions)."""
    rng = random.Random(seed)
    base, genesis = _genesis(n_chars)
    docs = [base] + [Doc(f"w{i}") for i in range(1, writers)]
    for d in docs[1:]:
        d.apply_change(genesis)
    batches = [[genesis]]
    comment_history = []
    for _ in range(steps):
        batch = []
        for w in range(rng.randrange(1, writers + 1)):
            doc = docs[rng.randrange(len(docs))]
            kind = rng.choice(
                ["insert", "insert", "insert", "delete", "addMark", "removeMark"]
            )
            if kind == "insert":
                op = _random_insert(rng, doc, 6)
            elif kind == "delete":
                op = _random_delete(rng, doc)
            elif kind == "addMark":
                op = _random_add_mark(rng, doc, comment_history)
            else:
                op = _random_remove_mark(rng, doc, comment_history, False)
            if op is not None:
                change, _ = doc.change([op])
                batch.append(change)
        # Sync everyone so later steps are causally clean.
        for change in batch:
            for d in docs:
                if d.actor_id != change["actor"]:
                    d.apply_change(change)
        if batch:
            batches.append(batch)
    return batches


def test_windowed_matches_full_random():
    """Randomized multi-writer streams, patched path: patches, planes,
    spans, digests and winner cache byte-identical, window engaged."""
    batches = _random_stream(0)
    _assert_identical(batches)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2])
def test_windowed_matches_full_random_slow(seed):
    """The wider seed matrix (PERITEXT_SLOW=1; tier-1 runs seed 0 plus the
    chaos growth-fuzz slice, which covers far more shapes per second)."""
    batches = _random_stream(seed)
    _assert_identical(batches)


def test_windowed_plain_merge_matches_full():
    """Same deliveries through the patch-free apply_changes path."""
    batches = _random_stream(10, steps=6)
    _assert_identical(batches, plain=True)


def test_zero_width_and_edge_marks():
    """Marks whose spans collapse at the window edges: a mark whose chars
    are all deleted (zero-width survivor), same-element anchors (the
    endOfText walk-order subtlety), and a mark ending exactly at a later
    edit's window boundary."""
    d, genesis = _genesis(400)
    batches = [[genesis]]
    c, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 100,
         "endIndex": 110, "markType": "strong"},
    ])
    batches.append([c])
    # Tombstone the whole marked span -> zero-width boundary pair.
    c, _ = d.change([
        {"path": ["text"], "action": "delete", "index": 100, "count": 10},
    ])
    batches.append([c])
    # Edit right at the collapsed mark.
    c, _ = d.change([
        {"path": ["text"], "action": "insert", "index": 100, "values": list("in")},
    ])
    batches.append([c])
    # Zero-width caret mark: start and end anchor the same element.
    c, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 200,
         "endIndex": 200, "markType": "em"},
    ])
    batches.append([c])
    c, _ = d.change([
        {"path": ["text"], "action": "insert", "index": 200, "values": list("zz")},
    ])
    batches.append([c])
    _assert_identical(batches)


def test_mark_anchored_at_earlier_mark_boundary():
    """Regression (growth-fuzz find): a mark whose start anchors exactly at
    an earlier mark's end boundary.  The start slot's carry source is the
    nearest defined slot AT OR LEFT of the start slot — the defined
    after-slot one past it must not satisfy the census (it is not a valid
    carry source), or the true source falls outside the window and the
    anchor write loses the earlier mark's bits."""
    d, genesis = _genesis(600)
    batches = [[genesis]]
    c, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 200,
         "endIndex": 210, "markType": "strong"},
    ])
    batches.append([c])
    # Starts landing on/next to the first mark's end boundary, both
    # parities, plus removeMark at the same seam.
    for start, end, mt, action in (
        (209, 215, "em", "addMark"),
        (210, 220, "em", "addMark"),
        (209, 214, "strong", "removeMark"),
        (208, 213, "comment", "addMark"),
    ):
        op = {"path": ["text"], "action": action, "startIndex": start,
              "endIndex": end, "markType": mt}
        if mt == "comment":
            op["attrs"] = {"id": "c-1"}
        c, _ = d.change([op])
        batches.append([c])
    _assert_identical(batches)


def test_tombstone_run_straddling_window_boundary():
    """A long tombstone run adjacent to the edit: the census hull must
    carry the skip-run slack over tombstones (they keep their slots)."""
    d, genesis = _genesis(500)
    batches = [[genesis]]
    c, _ = d.change([
        {"path": ["text"], "action": "delete", "index": 150, "count": 80},
    ])
    batches.append([c])
    # Insert right at the tombstone run's left edge, then inside what used
    # to be the run's span, then right after it.
    for idx in (150, 151, 149):
        c, _ = d.change([
            {"path": ["text"], "action": "insert", "index": idx, "values": list("ab")},
        ])
        batches.append([c])
    # And a mark spanning across the tombstone run.
    c, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 140,
         "endIndex": 160, "markType": "strong"},
    ])
    batches.append([c])
    _assert_identical(batches)


def test_over_window_fallback_full_doc_mark():
    """Batches the census cannot profitably bound — a mark spanning the
    whole document, edits at opposite ends — must fall back to the
    full-table path (no windowed launch for those batches) and still be
    byte-identical."""
    d, genesis = _genesis(900)
    batches = [[genesis]]
    c, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 0,
         "endIndex": 900, "markType": "strong"},
    ])
    batches.append([c])
    c, _ = d.change([
        {"path": ["text"], "action": "insert", "index": 1, "values": ["a"]},
        {"path": ["text"], "action": "insert", "index": 899, "values": ["b"]},
    ])
    batches.append([c])
    uw, _ = _assert_identical(batches, expect_windowed=False)
    # Every post-genesis batch here spans the table: all full-path.
    assert uw.stats.get("windowed_launches", 0) == 0


def test_census_rejection_backoff():
    """A streak of census rejections (persistently table-wide hulls) must
    trigger the backoff — the census is skipped for a few batches, every
    skipped batch rides the byte-identical full-table path, and a local
    edit after the skip window re-engages.  The stale mirror's rebuild is
    paid only where the device reach bound cannot rule the window out."""
    d, genesis = _genesis(900)
    batches = [[genesis]]
    # 12 consecutive whole-doc hulls: opposite-end edits.  The first 4
    # (threshold streak) are rejected by the reach bound, without a
    # rebuild; the next 8 land inside the skip window, so their census
    # never runs.
    for i in range(12):
        c, _ = d.change([
            {"path": ["text"], "action": "insert", "index": 1, "values": ["a"]},
            {"path": ["text"], "action": "insert", "index": 899 + 2 * i,
             "values": ["b"]},
        ])
        batches.append([c])
    # Skip window exhausted: a caret-local edit must re-engage the window.
    c, _ = d.change(
        [{"path": ["text"], "action": "insert", "index": 450, "values": ["e"]}]
    )
    batches.append([c])
    uw, _ = _assert_identical(batches)
    assert uw.stats.get("window_census_skips", 0) == 8, uw.stats
    assert uw.stats.get("windowed_launches", 0) == 1, uw.stats
    assert uw.stats.get("window_reach_rejects", 0) == 4, uw.stats
    # Only the final probe, which the reach bound lets through, rebuilds.
    assert uw.stats.get("window_rebuilds", 0) == 1, uw.stats


def test_window_engages_only_past_min_capacity():
    d, genesis = _genesis(100)
    c, _ = d.change(
        [{"path": ["text"], "action": "insert", "index": 50, "values": ["x"]}]
    )
    with window_env(True, min_cap="4096"):
        uni = TpuUniverse(["r1"], capacity=1024, max_mark_ops=64)
        uni.apply_changes_with_patches({"r1": [genesis]})
        uni.apply_changes_with_patches({"r1": [c]})
        assert uni.stats.get("windowed_launches", 0) == 0


def test_census_rejection_relaunches_full_path():
    """A corrupted mirror (simulating census drift) windows the wrong
    region; the device census check must reject it and the relaunched
    full-table path must produce the exact full-path results."""
    d, genesis = _genesis(800)
    warm, _ = d.change(
        [{"path": ["text"], "action": "insert", "index": 10, "values": ["w"]}]
    )
    edit1, _ = d.change(
        [{"path": ["text"], "action": "insert", "index": 700, "values": list("xy")}]
    )
    with window_env(True):
        uni = TpuUniverse(["r1"], capacity=2048, max_mark_ops=64)
        uni.apply_changes_with_patches({"r1": [genesis]})
        # Warm the mirror with a benign windowed ingest.
        uni.apply_changes_with_patches({"r1": [warm]})
        assert uni.stats.get("windowed_launches", 0) == 1
        # Corrupt the mirror: claim the element anchoring edit1's insert
        # lives near position 0 (swap two distant entries), so the census
        # windows the wrong region and the gathered window misses the ref.
        m = uni._mirror[0]
        tgt = 699  # edit1 references the element before index 700
        for f in ("ctr", "act", "deleted"):
            m[f][5], m[f][tgt] = m[f][tgt].copy(), m[f][5].copy()
        out = uni.apply_changes_with_patches({"r1": [edit1]})
        assert uni.stats.get("window_fallbacks", 0) == 1
    with window_env(False):
        ctrl = TpuUniverse(["r1"], capacity=2048, max_mark_ops=64)
        ctrl.apply_changes_with_patches({"r1": [genesis]})
        ctrl.apply_changes_with_patches({"r1": [warm]})
        ctrl_out = ctrl.apply_changes_with_patches({"r1": [edit1]})
    assert out == ctrl_out
    for f in STATE_FIELDS:
        assert (
            np.asarray(getattr(uni.states, f)) == np.asarray(getattr(ctrl.states, f))
        ).all(), f"plane {f} diverged after census rejection"


def test_nested_objects_alongside_windowed_text():
    """Host-object ops (nested maps/lists) interleave with windowed text
    edits; the merged host+device patch stream must match full-table."""
    docs, _, genesis = generate_docs("The windowed Peritext editor " * 14, 2)
    a, b = docs
    batches = [[genesis]]
    c1, _ = a.change([
        {"path": [], "action": "makeMap", "key": "meta"},
        {"path": ["meta"], "action": "set", "key": "title", "value": "w"},
        {"path": ["text"], "action": "insert", "index": 200, "values": list("hi")},
    ])
    b.apply_change(c1)
    batches.append([c1])
    c2, _ = b.change([
        {"path": ["text"], "action": "addMark", "startIndex": 195,
         "endIndex": 205, "markType": "strong"},
        {"path": ["meta"], "action": "set", "key": "title", "value": "x"},
    ])
    a.apply_change(c2)
    batches.append([c2])
    _assert_identical(batches)


def test_wcache_warm_identity_through_windowed_ingests():
    """A winner cache built by a full-table patched launch must survive
    windowed ingests byte-identically: window rows update through the
    gather/scatter, untouched rows persist."""
    d, genesis = _genesis(420)
    mark, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 50,
         "endIndex": 90, "markType": "strong"},
    ])
    edits = []
    c, _ = d.change([
        {"path": ["text"], "action": "insert", "index": 70, "values": list("mid")},
    ])
    edits.append(c)
    c, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 60,
         "endIndex": 80, "markType": "em"},
    ])
    edits.append(c)

    def run(windowed_later):
        uni = TpuUniverse(["r1"], capacity=1024, max_mark_ops=64)
        with window_env(False):
            uni.apply_changes_with_patches({"r1": [genesis]})
            # Full-table marked launch builds the persisted cache.
            uni.apply_changes_with_patches({"r1": [mark]})
        assert uni._wcaches is not None
        with window_env(windowed_later):
            for c in edits:
                uni.apply_changes_with_patches({"r1": [c]})
        return uni

    uw = run(True)
    uf = run(False)
    assert uw.stats.get("windowed_launches", 0) >= 1
    assert uw._wcaches is not None and uf._wcaches is not None
    assert (np.asarray(uw._wcaches) == np.asarray(uf._wcaches)).all()
    for f in STATE_FIELDS:
        assert (
            np.asarray(getattr(uw.states, f)) == np.asarray(getattr(uf.states, f))
        ).all()


@pytest.mark.chaos
def test_windowed_degrades_byte_identically_under_faults(monkeypatch):
    """Faults leg: a windowed ingest whose launch budget exhausts must
    complete on the oracle degrade path byte-identically, invalidate the
    mirror, and keep subsequent windowed ingests correct."""
    from peritext_tpu.runtime import faults

    monkeypatch.setenv("PERITEXT_LAUNCH_RETRIES", "1")
    monkeypatch.setenv("PERITEXT_LAUNCH_BACKOFF", "0.001")
    d, genesis = _genesis(420)
    e1, _ = d.change(
        [{"path": ["text"], "action": "insert", "index": 200, "values": list("!!")}]
    )
    e2, _ = d.change([
        {"path": ["text"], "action": "addMark", "startIndex": 198,
         "endIndex": 206, "markType": "strong"},
    ])
    e3, _ = d.change(
        [{"path": ["text"], "action": "insert", "index": 202, "values": ["z"]}]
    )

    def run(inject):
        with window_env(True):
            uni = TpuUniverse(["r1", "r2"], capacity=1024, max_mark_ops=64)
            outs = [uni.apply_changes_with_patches({"r1": [genesis], "r2": [genesis]})]
            outs.append(uni.apply_changes_with_patches({"r1": [e1], "r2": [e1]}))
            if inject:
                faults.install("seed=5;device_launch:fail=99")
            try:
                outs.append(uni.apply_changes_with_patches({"r1": [e2], "r2": [e2]}))
            finally:
                faults.reset()
            outs.append(uni.apply_changes_with_patches({"r1": [e3], "r2": [e3]}))
        return uni, outs

    uni_f, outs_f = run(inject=True)
    uni_c, outs_c = run(inject=False)
    assert uni_f.stats["degraded_batches"] == 1
    assert outs_f == outs_c
    for f in STATE_FIELDS:
        assert (
            np.asarray(getattr(uni_f.states, f)) == np.asarray(getattr(uni_c.states, f))
        ).all(), f"plane {f} diverged across the degrade seam"
    # The post-degrade ingest must have gone windowed again (mirror rebuilt).
    assert uni_f.stats.get("windowed_launches", 0) >= 2


@pytest.mark.chaos
def test_fuzz_chaos_slice_with_window_live():
    """A seeded fuzz --chaos slice with the window path live on the TpuDoc
    replicas (growth profile reaches window-eligible doc sizes)."""
    from peritext_tpu.fuzz import DEFAULT_CHAOS_SPEC, fuzz
    from peritext_tpu.ops.doc import TpuDoc

    with window_env(True, min_cap="64"):
        fuzz(
            iterations=12,
            seed=17,
            doc_factory=TpuDoc,
            chaos=DEFAULT_CHAOS_SPEC,
            chaos_quiesce=8,
            growth=True,
            growth_target=600,
            report_every=0,
        )
