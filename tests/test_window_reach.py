"""The window census's device reach bound (ops/window.py ``reach_queries``
/ ``reach_rejects``, kernels ``window_reach_bound``, TpuUniverse
``_reach_rejects``).

Where the causal mirror is stale, the universe asks the device for a lower
bound on the census's widest ``hull + inserts`` before it pays the mirror
rebuild, and takes the full-table path when that bound alone fails the
census's width test.  The bound may only reject a batch the census would
reject too: the property test holds it to that over random mirrors and
batches; the universe tests show it replaces the rebuild on full-table
rounds, byte-identically to the unwindowed path.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from peritext_tpu.ops import TpuUniverse
from peritext_tpu.ops import kernels as K
from peritext_tpu.ops import window as W
from peritext_tpu.oracle import Doc
from peritext_tpu.parallel.mesh import make_mesh
from peritext_tpu.testing import window_env

CAP = 256
REPLICAS = 3
ACTORS = 6

# Each case weights the kinds of row a random batch draws.
ROW_MIXES = {
    "head_inserts": {"head": 4, "insert": 1, "delete": 1},
    "chained_inserts": {"insert": 2, "chained": 4, "delete": 1},
    "born_deletes": {"insert": 2, "chained": 1, "born_delete": 4},
    "end_of_text_marks": {"insert": 1, "eot_mark": 4, "delete": 1},
    "created_anchor_marks": {"insert": 2, "chained": 1, "created_mark": 4},
    "unresolvable_refs": {"insert": 2, "delete": 2, "mark": 1, "unresolvable": 1},
    "mixed": {
        "head": 1, "insert": 2, "chained": 2, "delete": 2, "born_delete": 1,
        "mark": 2, "eot_mark": 1, "created_mark": 1, "unresolvable": 1,
    },
}


def _mirror(rng, n):
    """A random committed table of ``n`` unique ids, as the mirror holds it."""
    ctr = np.asarray(rng.sample(range(1, 8 * n + 8), n), np.int32)
    act = np.asarray([rng.randrange(ACTORS) for _ in range(n)], np.int32)
    deleted = np.asarray([rng.random() < 0.3 for _ in range(n)])
    bnd_def = np.asarray([rng.random() < 0.1 for _ in range(2 * n)])
    return W.make_mirror(ctr, act, deleted, bnd_def)


def _shuffled(rng, m):
    """``m``'s ids with a few swapped in place and fresh ones spliced in."""
    n = m["ctr"].shape[0]
    order = list(range(n))
    for _ in range(rng.randrange(4)):
        i, j = rng.randrange(n), rng.randrange(n)
        order[i], order[j] = order[j], order[i]
    extra = _mirror(rng, rng.randrange(1, 20))
    at = rng.randrange(n + 1)
    pick = lambda k: np.concatenate([m[k][order][:at], extra[k] + (10**5 if k == "ctr" else 0),
                                     m[k][order][at:]])
    bnd_def = np.asarray([rng.random() < 0.1 for _ in range(2 * (n + extra["ctr"].shape[0]))])
    return W.make_mirror(pick("ctr"), pick("act"), pick("deleted"), bnd_def)


def _row(kind, ctr=0, act=0, ref=(0, 0)):
    row = np.zeros(K.OP_FIELDS, np.int32)
    row[K.K_KIND], row[K.K_CTR], row[K.K_ACT] = kind, ctr, act
    row[K.K_REF_CTR], row[K.K_REF_ACT] = ref
    return row


def _mark(start, end, skind, ekind):
    row = _row(K.KIND_MARK)
    row[K.K_SCTR], row[K.K_SACT], row[K.K_SKIND] = start[0], start[1], skind
    row[K.K_ECTR], row[K.K_EACT], row[K.K_EKIND] = end[0], end[1], ekind
    return row


def _batch(rng, m, mix, size):
    """Random pre-fusion op rows in causal order against mirror ``m``."""
    committed = list(zip(m["ctr"].tolist(), m["act"].tolist()))
    created = []
    fresh = iter(range(10**6, 10**7))
    kinds = [k for k, w in mix.items() for _ in range(w)]
    rows = []

    def insert(ref):
        new = (next(fresh), rng.randrange(ACTORS))
        created.append(new)
        rows.append(_row(K.KIND_INSERT, new[0], new[1], ref))

    def anchor():
        pool = created if created and rng.random() < 0.3 else committed
        return rng.choice(pool)

    for _ in range(size):
        kind = rng.choice(kinds)
        if kind == "head":
            insert((0, 0))
        elif kind == "insert" or (kind in ("chained", "created_mark") and not created):
            insert(rng.choice(committed))
        elif kind == "chained":
            insert(rng.choice(created))
        elif kind == "delete":
            rows.append(_row(K.KIND_DELETE, ref=rng.choice(committed)))
        elif kind == "born_delete" and created:
            rows.append(_row(K.KIND_DELETE, ref=rng.choice(created)))
        elif kind == "mark":
            rows.append(_mark(anchor(), anchor(), rng.randrange(2), rng.randrange(2)))
        elif kind == "eot_mark":
            rows.append(_mark(anchor(), (0, 0), rng.randrange(2), 2))
        elif kind == "created_mark":
            rows.append(_mark(rng.choice(created), anchor(), rng.randrange(2),
                              rng.randrange(2)))
        elif kind == "unresolvable":
            ref = (next(fresh), rng.randrange(ACTORS))
            rows.append(_row(K.KIND_DELETE, ref=ref) if rng.random() < 0.5
                        else _row(K.KIND_INSERT, next(fresh), 0, ref))
    inserts = sum(int(r[K.K_KIND] == K.KIND_INSERT) for r in rows)
    return np.stack(rows) if rows else np.zeros((0, K.OP_FIELDS), np.int32), inserts


def _device_bound(mirrors, groups, group_of):
    """What the universe computes: the reach bound over the committed
    tables the mirrors were read from."""
    ctr = np.zeros((len(mirrors), CAP), np.int32)
    act = np.zeros((len(mirrors), CAP), np.int32)
    for r, m in enumerate(mirrors):
        n = m["ctr"].shape[0]
        ctr[r, :n], act[r, :n] = m["ctr"], m["act"]
    length = np.asarray([m["ctr"].shape[0] for m in mirrors], np.int32)
    ids, head = W.reach_queries([rows for rows, _ in groups])
    inserts = np.asarray([ins for _, ins in groups], np.int32)
    return int(K.window_reach_bound(
        jnp.asarray(ctr), jnp.asarray(act), jnp.asarray(length),
        jnp.asarray(ids[group_of]), jnp.asarray(head[group_of]),
        jnp.asarray(inserts[group_of]),
    ))


def _queries_loop(rows, width):
    """reach_queries for one batch, one row at a time: its reference."""
    made = {(int(r[K.K_CTR]), int(r[K.K_ACT])) for r in rows if r[K.K_KIND] == K.KIND_INSERT}
    head = False
    refs, starts, ends, targets = [], [], [], []
    for r in rows:
        ref = (int(r[K.K_REF_CTR]), int(r[K.K_REF_ACT]))
        if r[K.K_KIND] == K.KIND_INSERT:
            if ref == (0, 0):
                head = True
            else:
                refs.append(ref)
        elif r[K.K_KIND] == K.KIND_MARK:
            starts.append((int(r[K.K_SCTR]), int(r[K.K_SACT])))
            if r[K.K_EKIND] != 2:
                ends.append((int(r[K.K_ECTR]), int(r[K.K_EACT])))
        elif r[K.K_KIND] == K.KIND_DELETE:
            targets.append(ref)
    out = []
    for key in refs + starts + ends + targets:
        if key not in made and key not in out:
            out.append(key)
    return out[:width], head


def _host_reach(mirrors, groups, group_of):
    """Per replica, the span of the queried ids' positions plus its
    inserts, looked up on the host: the device bound's reference."""
    ids, head = W.reach_queries([rows for rows, _ in groups])
    out = []
    for r, m in enumerate(mirrors):
        g = group_of[r]
        table = {key: p for p, key in enumerate(zip(m["ctr"].tolist(), m["act"].tolist()))}
        at = [table[key] for key in map(tuple, ids[g].tolist()) if key in table]
        at += [0] * bool(head[g])
        out.append((max(at) - min(at) + 1 if at else 0) + groups[g][1])
    return out


@pytest.mark.parametrize("mix", sorted(ROW_MIXES))
def test_reach_bound_rejects_only_what_the_census_rejects(mix):
    """Over random mirrors and batches: reach_queries picks the ids its
    row-at-a-time reference picks, the device bound is the host's
    reach of the queried ids, no replica's census hull plus inserts falls
    below that reach, a batch the bound rejects is one plan_windows
    rejects on the same mirrors, and where plan_windows plans a window,
    the bound's width is no wider than the plan's."""
    rng = random.Random(f"reach-{mix}")
    ranks = np.asarray(rng.sample(range(ACTORS), ACTORS), np.int32)
    outcomes = {"rejected": 0, "passed": 0}
    for trial in range(40):
        first = _mirror(rng, rng.randrange(24, 200))
        # Replicas 0 and 1 share a group (one batch), as a stream group
        # does; replica 1 holds the same ids at other positions.
        mirrors = [first, _shuffled(rng, first), _mirror(rng, rng.randrange(24, 200))]
        group_of = np.asarray([0, 0, 1], np.int32)
        size = rng.randrange(1, 3 * W.REACH_IDS // 2)
        groups = [_batch(rng, mirrors[0], ROW_MIXES[mix], size),
                  _batch(rng, mirrors[2], ROW_MIXES[mix], size)]
        rows_of = [groups[g][0] for g in group_of]
        ins_of = [groups[g][1] for g in group_of]
        min_width = rng.choice([W.MIN_WINDOW, 8])
        ids, head = W.reach_queries([rows for rows, _ in groups])
        for g, (rows, _) in enumerate(groups):
            want, want_head = _queries_loop(rows, W.REACH_IDS)
            assert [tuple(i) for i in ids[g].tolist() if i != [-1, -1]] == want
            assert head[g] == want_head
        bound = _device_bound(mirrors, groups, group_of)
        reach = _host_reach(mirrors, groups, group_of)
        assert bound == max(reach), (mix, trial)
        for r, m in enumerate(mirrors):
            hull = W.replica_window(m, rows_of[r], ranks)
            if hull is not None:
                assert max(hull[1] - hull[0] + 1, 0) + ins_of[r] >= reach[r], (mix, trial, r)
        plan = W.plan_windows(mirrors, rows_of, ins_of, ranks, CAP, 0,
                              min_width=min_width)
        if W.reach_rejects(bound, CAP, min_width):
            outcomes["rejected"] += 1
            assert plan is None, (mix, trial, bound)
        else:
            outcomes["passed"] += 1
        if plan is not None:
            assert W.window_cap(bound, min_width) <= plan["w_cap"], (mix, trial)
    assert outcomes["rejected"] and outcomes["passed"], outcomes


def test_reach_queries_drop_created_ids_and_repeats():
    a, b, c = (5, 1), (7, 2), (9, 0)
    rows = np.stack([
        _row(K.KIND_INSERT, 20, 1, (0, 0)),  # head: no id
        _row(K.KIND_INSERT, 21, 1, (20, 1)),  # chained on a created id
        _row(K.KIND_DELETE, ref=b),
        _row(K.KIND_INSERT, 22, 1, a),
        _mark((21, 1), c, 0, 1),  # created start anchor, committed end
        _mark(a, (0, 0), 1, 2),  # endOfText: no end id
        _row(K.KIND_DELETE, ref=(22, 1)),  # a batch-born element
        _row(K.KIND_DELETE, ref=a),  # repeat
    ])
    ids, head = W.reach_queries([rows, rows[:0]], width=4)
    assert head.tolist() == [True, False]
    # Insert references, then mark anchors, then delete targets.
    assert ids[0].tolist() == [list(a), list(c), list(b), [-1, -1]]
    assert (ids[1] == -1).all()
    ids, _ = W.reach_queries([rows], width=2)
    assert ids[0].tolist() == [list(a), list(c)]


def _stream_rounds(n_chars=300, writers=3, rounds=4):
    """A genesis and ``rounds`` rounds of ``writers`` independent streams,
    each change editing both ends of its writer's text (as fleet-c4.rounds
    pins its smallest inserts) and deleting in the middle."""
    base = Doc("w0")
    genesis, _ = base.change([
        {"path": [], "action": "makeList", "key": "text"},
        {"path": ["text"], "action": "insert", "index": 0,
         "values": list(("reach bound " * 30)[:n_chars])},
    ])
    docs = [base] + [Doc(f"w{w}") for w in range(1, writers)]
    for doc in docs[1:]:
        doc.apply_change(genesis)
    per_round = []
    for _ in range(rounds):
        made = []
        for w, doc in enumerate(docs):
            length = sum(len(s["text"]) for s in doc.get_text_with_formatting(["text"]))
            change, _ = doc.change([
                {"path": ["text"], "action": "insert", "index": 0, "values": [str(w)]},
                {"path": ["text"], "action": "insert", "index": length + 1,
                 "values": list("end")},
                {"path": ["text"], "action": "delete", "index": length // 2, "count": 2},
            ])
            made.append(change)
        per_round.append(made)
    return genesis, per_round


@pytest.mark.parametrize("mesh", [None, (2, 2)], ids=["one_device", "mesh_2x2"])
def test_full_table_rounds_skip_the_mirror_rebuild(mesh):
    """A fleet of three stream groups (replica r merges streams r mod 3
    and r+1 mod 3), loaded fresh: every opposite-end round is rejected by
    the reach bound, no mirror is rebuilt, and the fleet ends
    byte-identical to the unwindowed path on one device.  On a (replica,
    seq) mesh the bound runs over the sharded table."""
    genesis, rounds = _stream_rounds()
    names = [f"f{r}" for r in range(6)]

    def drive(on, mesh=None):
        with window_env(on, min_cap="64"):
            uni = TpuUniverse(names, capacity=512, max_mark_ops=64)
            uni.apply_changes([[genesis]] * len(names))
            if mesh is not None:
                uni.shard(make_mesh(jax.devices()[: mesh[0] * mesh[1]], *mesh))
            seen = []
            for made in rounds:
                uni.apply_changes([[made[r % 3], made[(r + 1) % 3]] for r in range(len(names))])
                seen.append((uni.stats.get("window_reach_rejects", 0),
                             uni.stats.get("window_rebuilds", 0)))
            return uni, seen

    uw, seen = drive(True, mesh)
    uf, _ = drive(False)
    assert seen == [(k + 1, 0) for k in range(len(rounds))], seen
    assert uw.stats.get("window_reach_checks", 0) == len(rounds)
    assert uw.stats.get("windowed_launches", 0) == 0
    assert uf.stats.get("window_reach_checks", 0) == 0
    assert uw.texts() == uf.texts()
    assert uw.spans_batch() == uf.spans_batch()
    for f in ("elem_ctr", "elem_act", "deleted", "chars", "bnd_def", "bnd_mask",
              "mark_ctr", "mark_act", "length", "mark_count"):
        assert (np.asarray(getattr(uw.states, f)) == np.asarray(getattr(uf.states, f))).all(), f
