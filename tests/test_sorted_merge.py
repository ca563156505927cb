"""Sort-based batch integration must equal the sequential scan path exactly.

The placement proof (kernels.py) says simultaneous placement keyed by
(skip-run stop, descending op id) equals sequential RGA application; these
tests check it bit-for-bit against merge_step on randomized concurrent
workloads, deep reference chains, and adversarial same-position inserts.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from peritext_tpu.bench.workloads import build_device_batch, make_merge_workload
from peritext_tpu.ids import ActorRegistry
from peritext_tpu.ops import kernels as K
from peritext_tpu.ops.encode import (
    AttrRegistry,
    compute_rounds,
    encode_changes,
    fuse_insert_runs,
    split_rows,
)
from peritext_tpu.ops.state import make_empty_state, stack_states
from peritext_tpu.oracle import Doc


def sorted_inputs(text_rows_list, max_run=0):
    """Fuse + label rounds + pad via the shared production helper."""
    from peritext_tpu.ops.encode import prepare_sorted_batch

    sp = prepare_sorted_batch(text_rows_list, max_run=max_run)
    return (
        jnp.asarray(sp["text"]),
        jnp.asarray(sp["rounds"]),
        sp["num_rounds"],
        jnp.asarray(sp["bufs"]),
        sp["maxk"],
    )


def assert_states_equal(a, b, context=""):
    for field in dataclasses.fields(a):
        x = np.asarray(getattr(a, field.name))
        y = np.asarray(getattr(b, field.name))
        assert (x == y).all(), f"{context}: field {field.name} diverged"


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("with_marks", [True, False])
def test_sorted_merge_matches_scan_on_random_workloads(seed, with_marks):
    workload = make_merge_workload(
        doc_len=120, ops_per_merge=48, num_streams=4, with_marks=with_marks, seed=seed
    )
    batch = build_device_batch(workload, num_replicas=4, capacity=512, max_mark_ops=64)
    text_rows = [np.asarray(batch["text_ops"][r]) for r in range(4)]
    mark_ops = jnp.asarray(batch["mark_ops"])
    ranks = jnp.asarray(batch["ranks"])

    ref = K.merge_step_batch(
        batch["states"], jnp.asarray(batch["text_ops"]), mark_ops, ranks
    )
    text, ro, nr, buf, maxk = sorted_inputs(text_rows)
    out = K.merge_step_sorted_batch(
        batch["states"], text, ro, nr, mark_ops, ranks, buf, maxk
    )
    assert_states_equal(ref, out, f"seed={seed}")


def test_sorted_merge_deep_chains_and_same_position_races():
    """Adversarial: multiple actors inserting at the same position, chains
    of inserts referencing earlier batch elements, deletes of batch chars."""
    base = Doc("base")
    genesis, _ = base.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0, "values": list("wxyz")},
        ]
    )
    streams = []
    for name in ("alice", "bob", "carol"):
        w = Doc(name)
        w.apply_change(genesis)
        c1, _ = w.change(
            [{"path": ["text"], "action": "insert", "index": 2, "values": list(name[:2])}]
        )
        # Chain: type again right after the previous burst, then delete one
        # of this batch's own characters.
        c2, _ = w.change(
            [
                {"path": ["text"], "action": "insert", "index": 3, "values": list(name[2:].upper() or "Q")},
                {"path": ["text"], "action": "delete", "index": 2, "count": 1},
            ]
        )
        streams.append([c1, c2])

    actors = ActorRegistry()
    attrs = AttrRegistry()
    genesis_rows, _, _ = encode_changes([genesis], actors, attrs)
    text_obj = genesis["ops"][0]["opId"]
    merged_rows, _, _ = encode_changes(
        [c for s in streams for c in s], actors, attrs, text_obj=text_obj
    )
    ranks_np = np.zeros(64, np.int32)
    rk = actors.ranks()
    ranks_np[: len(rk)] = rk
    ranks = jnp.asarray(ranks_np)

    base_state = K.apply_ops_jit(
        make_empty_state(128, 64), jnp.asarray(genesis_rows), ranks
    )
    states = stack_states([base_state])
    text_rows, mark_rows = split_rows(merged_rows)
    assert mark_rows.shape[0] == 0

    ref = K.merge_step_batch(
        states,
        jnp.asarray(text_rows[None, ...]),
        jnp.zeros((1, 1, K.OP_FIELDS), jnp.int32),
        ranks,
    )
    text, ro, nr, buf, maxk = sorted_inputs([text_rows])
    assert nr >= 2  # the chains force multiple rounds
    out = K.merge_step_sorted_batch(
        states, text, ro, nr, jnp.zeros((1, 1, K.OP_FIELDS), jnp.int32), ranks, buf, maxk
    )
    assert_states_equal(ref, out, "deep chains")


def test_sorted_merge_unbounded_run_is_single_round():
    """A pasted 300-char document fuses to one run row placed in one round."""
    doc = Doc("paster")
    doc.change([{"path": [], "action": "makeList", "key": "text"}])
    change, _ = doc.change(
        [{"path": ["text"], "action": "insert", "index": 0, "values": list("ab" * 150)}]
    )
    actors, attrs = ActorRegistry(), AttrRegistry()
    rows, _, _ = encode_changes(
        [change], actors, attrs, text_obj=change["ops"][0].get("obj")
    )
    fused, _, _ = fuse_insert_runs(rows, max_run=0)
    assert fused.shape[0] == 1
    ro, nr = compute_rounds(fused)
    assert nr == 1

    ranks = jnp.asarray(np.zeros(8, np.int32))
    states = stack_states([make_empty_state(512, 32)])
    ref = K.merge_step_batch(
        states,
        jnp.asarray(rows[None, ...]),
        jnp.zeros((1, 1, K.OP_FIELDS), jnp.int32),
        ranks,
    )
    text, ro2, nr2, buf, maxk = sorted_inputs([rows])
    assert maxk >= 300  # one 300-char block (bucketed)
    out = K.merge_step_sorted_batch(
        states, text, ro2, nr2, jnp.zeros((1, 1, K.OP_FIELDS), jnp.int32), ranks, buf, maxk
    )
    assert_states_equal(ref, out, "unbounded run")


def test_universe_falls_back_to_scan_on_deep_histories():
    """A deep single-writer history (end-appends chained through elements
    created by earlier changes, interleaved so run fusion can't flatten the
    chain) exceeds the sorted path's round budget; the universe must fall
    back to the scan path — observable via stats — and match the oracle."""
    import os

    from peritext_tpu.ops import TpuUniverse
    from peritext_tpu.testing import generate_docs

    if os.environ.get("PERITEXT_MERGE_PATH") == "scan":
        pytest.skip("scan path forced; fallback branch not reachable")

    docs, _, genesis = generate_docs("deep")
    writer = docs[0]
    changes = [genesis]
    for i in range(40):
        if i % 2 == 0:
            idx = len(writer.root["text"])  # chain: references previous append
        else:
            idx = 0  # breaks row adjacency so fusion can't flatten the chain
        change, _ = writer.change(
            [{"path": ["text"], "action": "insert", "index": idx, "values": [chr(97 + i % 26)]}]
        )
        changes.append(change)

    uni = TpuUniverse(["r"], capacity=256)
    uni.apply_changes({"r": changes})
    assert uni.stats["scan_fallbacks"] == 1, "fallback branch did not trigger"
    assert uni.spans("r") == writer.get_text_with_formatting(["text"])


def test_chunked_sorted_merge_matches_unchunked():
    """The R-chunking memory valve (uneven tail included) is bit-exact."""
    workload = make_merge_workload(
        doc_len=80, ops_per_merge=32, num_streams=3, with_marks=True, seed=9
    )
    batch = build_device_batch(workload, num_replicas=7, capacity=256, max_mark_ops=64)
    text, ro, nr, buf, maxk = sorted_inputs(
        [np.asarray(batch["text_ops"][r]) for r in range(7)]
    )
    mark_ops = jnp.asarray(batch["mark_ops"])
    ranks = jnp.asarray(batch["ranks"])
    ref = K.merge_step_sorted_batch(
        batch["states"], text, ro, nr, mark_ops, ranks, buf, maxk
    )
    out = K.merge_step_sorted_batch(
        batch["states"], text, ro, nr, mark_ops, ranks, buf, maxk, chunk=3
    )
    assert_states_equal(ref, out, "chunked")


def _text_op(action, **kw):
    return {"path": ["text"], "action": action, **kw}


def _ins(index, chars):
    return _text_op("insert", index=index, values=list(chars))


def _mark(action, start, end, mark_type, attrs=None):
    op = _text_op(
        action, startIndex=start, endIndex=end, markType=mark_type
    )
    if attrs is not None:
        op["attrs"] = attrs
    return op


def _genesis(doc, text):
    change, _ = doc.change(
        [{"path": [], "action": "makeList", "key": "text"}, _ins(0, text)]
    )
    return change


def _writers(names, base_changes):
    docs = [Doc(n) for n in names]
    for d in docs:
        for ch in base_changes:
            d.apply_change(ch)
    return docs


def _case_concurrent_marks():
    """Two concurrent streams with inserts, a delete and marks, over a doc
    that already holds marks: one insert lands on a mark's start boundary,
    so that defined slot moves and the new elements' slots start empty."""
    base = Doc("base")
    g = _genesis(base, "The quick brown fox jumps over the lazy dog")
    m, _ = base.change([_mark("addMark", 4, 15, "strong")])
    alice, bob = _writers(("alice", "bob"), [g, m])
    a1, _ = alice.change([_ins(10, "AAA"), _mark("addMark", 2, 20, "em")])
    a2, _ = alice.change([_mark("removeMark", 5, 8, "strong")])
    b1, _ = bob.change([_ins(4, "BB"), _text_op("delete", index=30, count=3)])
    b2, _ = bob.change([_mark("addMark", 0, 12, "link", {"url": "b.com"})])
    return [g, m], [a1, a2, b1, b2]


def _case_chained_rounds():
    """Inserts referencing the batch's own inserts: several placement
    rounds, each moving the boundary tables again."""
    base = Doc("base")
    g = _genesis(base, "0123456789abcdef")
    m, _ = base.change([_mark("addMark", 2, 12, "em")])
    (w,) = _writers(("w",), [g, m])
    c1, _ = w.change([_ins(5, "abc")])
    c2, _ = w.change([_ins(8, "def")])
    c3, _ = w.change([_ins(0, "Z"), _ins(12, "gh"), _mark("addMark", 5, 13, "strong")])
    return [g, m], [c1, c2, c3]


def _case_genesis():
    """The genesis change and its first marks merged into an empty doc."""
    w = Doc("w")
    g = _genesis(w, "hello brave new world")
    c1, _ = w.change(
        [_mark("addMark", 0, 5, "strong"), _mark("addMark", 6, 21, "comment", {"id": "c1"})]
    )
    c2, _ = w.change([_ins(5, ","), _mark("removeMark", 2, 4, "strong")])
    return [], [g, c1, c2]


def _case_tombstones():
    """Most of the doc is tombstones: inserts and mark anchors land among
    deleted elements, whose boundary slots still carry pre-batch marks."""
    base = Doc("base")
    g = _genesis(base, "".join(chr(97 + i % 26) for i in range(80)))
    m, _ = base.change([_mark("addMark", 10, 50, "strong")])
    d, _ = base.change([_text_op("delete", index=5, count=60)])
    alice, bob = _writers(("alice", "bob"), [g, m, d])
    a1, _ = alice.change([_ins(3, "xyz"), _text_op("delete", index=8, count=2)])
    a2, _ = alice.change([_mark("addMark", 0, 10, "em")])
    b1, _ = bob.change([_ins(5, "Q"), _mark("addMark", 2, 6, "link", {"url": "t.com"})])
    return [g, m, d], [a1, a2, b1]


def _case_edge_anchors():
    """Marks anchored on the first and last element and on endOfText, while
    a concurrent writer inserts at both ends."""
    base = Doc("base")
    g = _genesis(base, "edges")
    m, _ = base.change([_mark("addMark", 1, 3, "em")])
    alice, bob = _writers(("alice", "bob"), [g, m])
    a1, _ = alice.change(
        [
            _mark("addMark", 0, 5, "strong"),
            _mark("addMark", 0, 1, "link", {"url": "first.com"}),
            _mark("addMark", 4, 5, "em"),
            _mark("addMark", 0, 5, "comment", {"id": "all"}),
            _mark("removeMark", 0, 1, "strong"),
            _mark("removeMark", 4, 5, "em"),
        ]
    )
    b1, _ = bob.change([_ins(0, "<"), _ins(6, ">")])
    b2, _ = bob.change([_mark("addMark", 6, 7, "strong")])
    return [g, m], [a1, b1, b2]


def _case_deep_mark_batch():
    """More than 64 mark ops in one batch: 2M > 128 write nodes take the
    node tables' gather expansion instead of the select chains."""
    import random

    rng = random.Random(24)
    base = Doc("base")
    g = _genesis(base, "".join(chr(65 + i % 26) for i in range(100)))
    m, _ = base.change([_mark("addMark", 20, 70, "em")])
    (w,) = _writers(("w",), [g, m])
    changes = []
    for i in range(70):
        a = rng.randrange(0, 95)
        end = min(a + 1 + rng.randrange(30), 100)
        mt = rng.choice(["strong", "em", "link"])
        add = mt == "link" or bool(i % 3)
        ops = [
            _mark(
                "addMark" if add else "removeMark", a, end, mt,
                {"url": f"u{i}.com"} if mt == "link" else None,
            )
        ]
        if i % 10 == 0:
            ops.append(_ins(rng.randrange(0, 90), "ij"))
        ch, _ = w.change(ops)
        changes.append(ch)
    return [g, m], changes


_DIFF_CASES = {
    "concurrent_marks": (_case_concurrent_marks, {}),
    "chained_rounds": (_case_chained_rounds, {"min_rounds": 2}),
    "genesis": (_case_genesis, {}),
    "tombstones": (_case_tombstones, {}),
    "edge_anchors": (_case_edge_anchors, {}),
    "deep_mark_batch": (_case_deep_mark_batch, {"min_marks": 65}),
}


@pytest.mark.parametrize("case", sorted(_DIFF_CASES))
def test_sorted_merge_matches_scan_on_edge_cases(case):
    """Field by field on every DocState plane, the sorted merge (placement
    by shift passes with the boundary tables riding them, then the batched
    mark phase) equals the sequential scan merge on targeted batches."""
    build, expect = _DIFF_CASES[case]
    base_changes, batch_changes = build()
    actors, attrs = ActorRegistry(), AttrRegistry()
    text_obj = None
    base_rows = np.zeros((0, K.OP_FIELDS), np.int32)
    if base_changes:
        base_rows, _, counts = encode_changes(base_changes, actors, attrs)
        text_obj = counts["text_obj"]
    rows, _, _ = encode_changes(batch_changes, actors, attrs, text_obj=text_obj)
    text_rows, mark_rows = split_rows(rows)
    assert mark_rows.shape[0] >= expect.get("min_marks", 1)
    ranks_np = np.zeros(64, np.int32)
    rk = actors.ranks()
    ranks_np[: len(rk)] = rk
    ranks = jnp.asarray(ranks_np)

    state = make_empty_state(256, 128)
    if base_rows.shape[0]:
        state = K.apply_ops_jit(state, jnp.asarray(base_rows), ranks)
    states = stack_states([state])
    marks = jnp.asarray(mark_rows[None, ...])

    ref = K.merge_step_batch(states, jnp.asarray(text_rows[None, ...]), marks, ranks)
    text, ro, nr, buf, maxk = sorted_inputs([text_rows])
    assert nr >= expect.get("min_rounds", 1)
    out = K.merge_step_sorted_batch(states, text, ro, nr, marks, ranks, buf, maxk)
    assert_states_equal(ref, out, case)
    assert int(np.asarray(out.mark_count)[0]) > 0


def test_mark_window_clamps_at_table_end():
    """The r5 word-windowed mark accumulation clamps its window when
    mark_count sits in the table's last words (w0 = clip(count//32,
    0, W - w_act)): marks landing there must still be bit-exact with the
    sequential scan.  Builds a replica whose table holds 100 ops (of 128,
    W=4), then merges one more batch through both paths."""
    import random

    from peritext_tpu.ops.encode import prepare_sorted_batch

    base = Doc("base")
    genesis, _ = base.change(
        [
            {"path": [], "action": "makeList", "key": "text"},
            {"path": ["text"], "action": "insert", "index": 0, "values": list("y" * 200)},
        ]
    )
    w = Doc("w")
    w.apply_change(genesis)
    rng = random.Random(13)

    def mark_batch(n):
        changes = []
        for i in range(n):
            a = rng.randrange(0, 150)
            add = bool(i % 4)
            mt = rng.choice(["strong", "em", "link"] if add else ["strong", "em"])
            op = {
                "path": ["text"],
                "action": "addMark" if add else "removeMark",
                "startIndex": a,
                "endIndex": a + 1 + rng.randrange(40),
                "markType": mt,
            }
            if mt == "link":
                op["attrs"] = {"url": "u.com"}
            ch, _ = w.change([op])
            changes.append(ch)
        return changes

    actors, attrs = ActorRegistry(), AttrRegistry()
    g_rows, _, _ = encode_changes([genesis], actors, attrs)
    text_obj = genesis["ops"][0]["opId"]
    ranks = np.zeros(64, np.int32)
    st = stack_states([make_empty_state(512, 128)])
    g_text, g_marks = split_rows(g_rows)
    sp0 = prepare_sorted_batch([g_text])
    gmr = np.zeros((1, max(g_marks.shape[0], 1), K.OP_FIELDS), np.int32)
    gmr[0, : g_marks.shape[0]] = g_marks
    rk = actors.ranks()
    ranks[: len(rk)] = rk
    st = K.merge_step_sorted_batch(
        st, jnp.asarray(sp0["text"]), jnp.asarray(sp0["rounds"]), sp0["num_rounds"],
        jnp.asarray(gmr), jnp.asarray(ranks), jnp.asarray(sp0["bufs"]), sp0["maxk"],
    )

    def ingest_both(st_in, changes):
        rows, _, _ = encode_changes(changes, actors, attrs, text_obj=text_obj)
        t, m = split_rows(rows)
        rk = actors.ranks()
        ranks[: len(rk)] = rk
        sp = prepare_sorted_batch([t])
        srt = K.merge_step_sorted_batch(
            st_in, jnp.asarray(sp["text"]), jnp.asarray(sp["rounds"]),
            sp["num_rounds"], jnp.asarray(m[None, ...]), jnp.asarray(ranks),
            jnp.asarray(sp["bufs"]), sp["maxk"],
        )
        scn = K.merge_step_batch(
            st_in, jnp.asarray(t[None, ...]), jnp.asarray(m[None, ...]),
            jnp.asarray(ranks),
        )
        return srt, scn

    # Fill to mark_count=100; the fill rounds double as free differential
    # coverage at mark_count 25/50/75/100 (windows sliding up the table).
    for i in range(4):
        srt, scn = ingest_both(st, mark_batch(25))
        assert_states_equal(srt, scn, f"fill round {i}")
        st = srt
    assert int(np.asarray(st.mark_count)[0]) == 100

    # The batch under test: its window starts in the table's final words.
    srt, scn = ingest_both(st, mark_batch(10))
    assert_states_equal(srt, scn, "clamped window")
