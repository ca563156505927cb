"""Jitted CRDT kernels over the dense DocState.

Each of the reference's O(n) pointer-walk hot loops (SURVEY.md §3) becomes a
vectorized tensor computation here:

- ``findListElement`` / id lookup (micromerge.ts:731-755)  -> masked argmax
- the concurrent-insert skip scan (micromerge.ts:630-635)  -> masked min over
  a comparison vector (the skip run is contiguous, so its end is the first
  non-skippable position)
- metadata splice (micromerge.ts:638)                      -> masked shift
- ``applyAddRemoveMark``'s 2n-position walk with carried op
  sets (peritext.ts:154-223)                               -> prefix cummax
  carry + bitset algebra over boundary-mask rows
- ``getTextWithFormatting``'s left-inheritance walk
  (peritext.ts:366-390)                                    -> segmented
  carry via cummax over per-element boundary sources

All kernels are pure ``DocState -> DocState`` functions of statically-shaped
arrays: `jit`/`vmap`/`shard_map` compose over them, and `lax.scan` sequences
ops within a causal batch while replicas stay embarrassingly parallel.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from peritext_tpu.ops.state import MASK_WORD_BITS, DocState
# Mark-type allowMultiple flags arrive as a runtime input vector
# (schema.allow_multiple_array) so registered mark types take effect
# without stale jit constants.

# Op-row field indices (see encode.py for the host-side encoder).
K_KIND = 0  # 0 pad, 1 insert, 2 delete, 3 mark
K_CTR = 1
K_ACT = 2
K_REF_CTR = 3  # insert: reference elem (0 = HEAD); delete: target elem
K_REF_ACT = 4
K_PAYLOAD = 5  # insert: codepoint
K_MACTION = 6  # 0 addMark, 1 removeMark
K_MTYPE = 7
K_MATTR = 8
K_SKIND = 9  # start boundary: 0 before, 1 after
K_SCTR = 10
K_SACT = 11
K_EKIND = 12  # end boundary: 0 before, 1 after, 2 endOfText
K_ECTR = 13
K_EACT = 14
OP_FIELDS = 15

KIND_PAD = 0
KIND_INSERT = 1
KIND_DELETE = 2
KIND_MARK = 3
# Fast-path only: a fused run of chained inserts (see _apply_text_op).
# Fields: K_CTR = first op counter, K_REF_* = the run's reference element,
# K_PAYLOAD = offset into the side char buffer, K_RUN_LEN = run length.
KIND_INSERT_RUN = 4
K_RUN_LEN = K_MACTION  # field reuse; insert runs carry no mark fields
MAX_RUN_LEN = 64


def _find_elem(state: DocState, ctr, act):
    """Index of the element created by op (ctr@act); (C, found=False) if absent."""
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length
    match = live & (state.elem_ctr == ctr) & (state.elem_act == act)
    found = jnp.any(match)
    return jnp.argmax(match).astype(jnp.int32), found


def _rga_insert_position(elem_ctr, elem_act, length, op, ranks):
    """RGA insert position (reference micromerge.ts:614-635).

    Position = after the reference element, then past the contiguous run of
    elements whose ids exceed this op's id — the convergence rule for
    concurrent same-position inserts (micromerge.ts:630-635).  The run is
    contiguous by construction, so its end is the first position at or after
    ref+1 that is dead or has a smaller id.  Shared by the faithful per-op
    path and the fast two-phase path so their tie-breaks can never diverge.
    Returns (t, keep, here) masks for masked-shift splicing.
    """
    c = elem_ctr.shape[0]
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < length

    is_head = (op[K_REF_CTR] == 0) & (op[K_REF_ACT] == 0)
    match = live & (elem_ctr == op[K_REF_CTR]) & (elem_act == op[K_REF_ACT])
    idx = jnp.where(is_head, jnp.int32(-1), jnp.argmax(match).astype(jnp.int32))

    op_rank = ranks[op[K_ACT]]
    elem_gt_op = (elem_ctr > op[K_CTR]) | (
        (elem_ctr == op[K_CTR]) & (ranks[elem_act] > op_rank)
    )
    stop = (ar > idx) & ~(live & elem_gt_op)
    t = jnp.min(jnp.where(stop, ar, c)).astype(jnp.int32)
    return t, ar < t, ar == t


def _apply_insert(state: DocState, op, ranks) -> DocState:
    """RGA insert (reference micromerge.ts:614-672)."""
    c = state.capacity
    t, keep, here = _rga_insert_position(
        state.elem_ctr, state.elem_act, state.length, op, ranks
    )

    def splice(arr, value):
        return jnp.where(keep, arr, jnp.where(here, value, jnp.roll(arr, 1)))

    slot_ar = jnp.arange(2 * c, dtype=jnp.int32)
    slot_keep = slot_ar < 2 * t
    slot_new = (slot_ar == 2 * t) | (slot_ar == 2 * t + 1)
    bnd_def = jnp.where(slot_keep, state.bnd_def, jnp.where(slot_new, False, jnp.roll(state.bnd_def, 2)))
    bnd_mask = jnp.where(
        slot_keep[:, None],
        state.bnd_mask,
        jnp.where(slot_new[:, None], jnp.uint32(0), jnp.roll(state.bnd_mask, 2, axis=0)),
    )

    return DocState(
        elem_ctr=splice(state.elem_ctr, op[K_CTR]),
        elem_act=splice(state.elem_act, op[K_ACT]),
        deleted=splice(state.deleted, False),
        chars=splice(state.chars, op[K_PAYLOAD]),
        bnd_def=bnd_def,
        bnd_mask=bnd_mask,
        mark_ctr=state.mark_ctr,
        mark_act=state.mark_act,
        mark_action=state.mark_action,
        mark_type=state.mark_type,
        mark_attr=state.mark_attr,
        length=state.length + 1,
        mark_count=state.mark_count,
    )


def _apply_delete(state: DocState, op, ranks) -> DocState:
    """Tombstone the target element (reference micromerge.ts:677-724).

    Idempotent: re-deleting is a no-op, matching applyListUpdate's
    already-deleted guard (micromerge.ts:689).
    """
    del ranks
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length
    match = live & (state.elem_ctr == op[K_REF_CTR]) & (state.elem_act == op[K_REF_ACT])
    return dataclasses.replace(state, deleted=state.deleted | match)


def _mark_slot_context(state: DocState, op):
    """Shared boundary-slot context for mark application and patch signals.

    Returns (s_slot, e_slot, slots, defined, carry, src) where carry[p] is
    the nearest pre-op defined set at or left of p (the walk's currentOps,
    peritext.ts:181-186) and src[p] is that set's slot index (-1: none —
    the winner cache gathers through it).  Shared so the patch signals can
    never desynchronize from the state the op actually writes.
    """
    c = state.capacity
    big = jnp.int32(2 * c + 2)
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length

    s_match = live & (state.elem_ctr == op[K_SCTR]) & (state.elem_act == op[K_SACT])
    s_slot = 2 * jnp.argmax(s_match).astype(jnp.int32) + op[K_SKIND]
    e_match = live & (state.elem_ctr == op[K_ECTR]) & (state.elem_act == op[K_EACT])
    e_slot = jnp.where(
        op[K_EKIND] == 2,
        big,
        2 * jnp.argmax(e_match).astype(jnp.int32) + jnp.minimum(op[K_EKIND], 1),
    )

    # Walk-order subtlety: when start and end anchor the same slot the
    # walk's start branch fires first and the end match can never fire
    # afterwards (calculateOpsForPosition checks start before end,
    # peritext.ts:236-241), so the op extends to the end of the document —
    # exactly the endOfText behavior.
    e_slot = jnp.where(e_slot == s_slot, big, e_slot)

    slots = jnp.arange(2 * c, dtype=jnp.int32)
    defined = state.bnd_def & (slots < 2 * state.length)
    src = lax.cummax(jnp.where(defined, slots, jnp.int32(-1)))
    carry = jnp.where(
        (src >= 0)[:, None], state.bnd_mask[jnp.maximum(src, 0)], jnp.uint32(0)
    )
    return s_slot, e_slot, slots, defined, carry, src


def _apply_mark(state: DocState, op, ranks) -> DocState:
    """Write a mark op into the boundary bitsets (reference peritext.ts:154-223).

    Vectorized form of the BEFORE/DURING/AFTER walk.  Derivation (preserving
    a reference subtlety): the walk's carried ``currentOps`` is never updated
    with the op being applied (peritext.ts:181-186), so every write stores
    ``carry_old | op_bit`` for slots in [start, end) and plain ``carry_old``
    at the end slot, where ``carry_old[p]`` is the nearest *pre-op* defined
    set at or left of p.  Written slots: the start slot, every already-defined
    slot strictly inside the range, and the end slot.  If the end slot
    precedes the start slot in walk order, the walk hits AFTER first and only
    the end slot is written (with its carry), the op lands nowhere.
    """
    del ranks
    return _apply_mark_ctx(state, op, _mark_slot_context(state, op))


def _apply_mark_ctx(state: DocState, op, ctx) -> DocState:
    """_apply_mark with a precomputed _mark_slot_context (so a patch-signal
    computation sharing the same instant can reuse one context)."""
    s_slot, e_slot, slots, defined, carry, _ = ctx
    m = state.mark_count
    word = m // MASK_WORD_BITS
    bit = jnp.uint32(1) << (m % MASK_WORD_BITS).astype(jnp.uint32)
    op_bit_row = jnp.zeros_like(state.bnd_mask[0]).at[word].set(bit)

    s_lt_e = s_slot < e_slot
    in_range = (slots >= s_slot) & (slots < e_slot) & s_lt_e
    write = (in_range & ((slots == s_slot) | defined)) | (slots == e_slot)

    new_rows = carry | jnp.where(in_range[:, None], op_bit_row, jnp.uint32(0))
    bnd_mask = jnp.where(write[:, None], new_rows, state.bnd_mask)
    bnd_def = state.bnd_def | write

    return DocState(
        elem_ctr=state.elem_ctr,
        elem_act=state.elem_act,
        deleted=state.deleted,
        chars=state.chars,
        bnd_def=bnd_def,
        bnd_mask=bnd_mask,
        mark_ctr=state.mark_ctr.at[m].set(op[K_CTR]),
        mark_act=state.mark_act.at[m].set(op[K_ACT]),
        mark_action=state.mark_action.at[m].set(op[K_MACTION]),
        mark_type=state.mark_type.at[m].set(op[K_MTYPE]),
        mark_attr=state.mark_attr.at[m].set(op[K_MATTR]),
        length=state.length,
        mark_count=m + 1,
    )


def apply_op(state: DocState, op: jax.Array, ranks: jax.Array) -> DocState:
    """Apply one encoded internal op.  ``op`` is an OP_FIELDS int32 row."""
    kind = jnp.clip(op[K_KIND], 0, 3)
    return lax.switch(
        kind,
        [
            lambda s, o, r: s,  # pad
            _apply_insert,
            _apply_delete,
            _apply_mark,
        ],
        state,
        op,
        ranks,
    )


def apply_ops(state: DocState, ops: jax.Array, ranks: jax.Array) -> DocState:
    """Sequence a causally-ordered op batch with lax.scan.

    Within one replica ops are sequentially dependent (an insert's position
    depends on prior inserts — SURVEY.md §7 "hard parts"); across replicas
    this function vmaps, which is the throughput axis.
    """

    def step(s, op):
        return apply_op(s, op, ranks), None

    final, _ = lax.scan(step, state, ops)
    return final


apply_ops_jit = jax.jit(apply_ops)
apply_ops_vmapped = jax.vmap(apply_ops, in_axes=(0, 0, None))
apply_ops_batch = jax.jit(apply_ops_vmapped)


# ---------------------------------------------------------------------------
# Patch-emitting faithful path (the incremental codepath on device)
# ---------------------------------------------------------------------------


def _walk_signals(ctx, visible, c: int):
    """written/during/visibleIndex planes of the reference mark walk
    (peritext.ts:181-214), from a precomputed slot context.  Shared by the
    interleaved signals and the sorted patch scan so the walk semantics
    (incl. the s_lt_e/endOfText edge) have exactly one definition."""
    s_slot, e_slot, slots, defined, _carry, _src = ctx
    s_lt_e = s_slot < e_slot
    during = (slots >= s_slot) & (slots < e_slot) & s_lt_e
    written = (during & ((slots == s_slot) | defined)) | (slots == e_slot)
    # visibleIndex per slot: before-slot of element i sees the count of
    # visible elements before i; after-slot sees the count through i.
    vcum = jnp.cumsum(visible.astype(jnp.int32))
    vis = jnp.stack([vcum - visible.astype(jnp.int32), vcum], axis=1).reshape(2 * c)
    final_vis = vcum[c - 1] if c > 0 else jnp.int32(0)
    return written, during, vis, final_vis


def _changed_vs_winner(op, op_rank, w_ctr, w_rank, w_action, w_attr, has_winner):
    """The `opsToMarks(current) != opsToMarks(new)` test against the op's
    group winner (reference peritext.ts:294-326 restricted to one group):
    the op must win the LWW tie-break AND flip the effective value.  One
    definition shared by both patch paths."""
    op_wins = ~has_winner | (op[K_CTR] > w_ctr) | (
        (op[K_CTR] == w_ctr) & (op_rank > w_rank)
    )
    old_active = has_winner & (w_action == 0)
    new_active = op[K_MACTION] == 0
    value_differs = (old_active != new_active) | (
        old_active & new_active & (w_attr != op[K_MATTR])
    )
    return op_wins & value_differs


def _mark_patch_signals(state: DocState, op, ranks, multi):
    """Per-slot patch signals for a mark op (reference peritext.ts:181-214).

    Returns (written, during, changed, vis, final_vis):
    - written[p]: the walk writes slot p (start slot, defined slots strictly
      inside the range, end slot)
    - during[p]: the DURING window [start, end)
    - changed[p]: adding this op to slot p's inherited set changes the
      *effective* marks there — the `opsToMarks(current) != opsToMarks(new)`
      test, restricted to the op's own resolution group (its mark type, or
      its (type, comment-id) group for allowMultiple marks), because adding
      one op cannot change any other group's resolution
    - vis[p]: the reference walk's visibleIndex at slot p's patch logic
    - final_vis: total visible length (also objLength for patch clamping)
    """
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length

    ctx = _mark_slot_context(state, op)
    carry = ctx[4]
    written, during, vis, final_vis = _walk_signals(ctx, live & ~state.deleted, c)

    # Inherited (pre-op) sets at every slot, as presence bits.
    present = expand_mask_bits(carry, state.max_mark_ops)  # [2C, M]

    # Winner of the op's own resolution group per slot.
    m_live = jnp.arange(state.max_mark_ops, dtype=jnp.int32) < state.mark_count
    is_multi = multi[op[K_MTYPE]]
    group = m_live & (state.mark_type == op[K_MTYPE]) & (
        ~is_multi | (state.mark_attr == op[K_MATTR])
    )
    cand = present & group[None, :]
    # Two-pass lexicographic argmax on (ctr, rank) without int64:
    rank = ranks[state.mark_act]
    neg = jnp.int32(-(2**31) + 1)
    ctrs = jnp.where(cand, state.mark_ctr[None, :], neg)
    max_ctr = jnp.max(ctrs, axis=1)  # [2C]
    tie = cand & (state.mark_ctr[None, :] == max_ctr[:, None])
    rks = jnp.where(tie, rank[None, :], neg)
    max_rank = jnp.max(rks, axis=1)
    win = tie & (rank[None, :] == max_rank[:, None])  # one-hot winner per slot
    has_winner = jnp.any(cand, axis=1)

    w_action = jnp.sum(jnp.where(win, state.mark_action[None, :], 0), axis=1)
    w_attr = jnp.sum(jnp.where(win, state.mark_attr[None, :], 0), axis=1)
    w_ctr = jnp.where(has_winner, max_ctr, jnp.int32(-1))
    w_rank = jnp.where(has_winner, max_rank, jnp.int32(-1))

    changed = _changed_vs_winner(
        op, ranks[op[K_ACT]], w_ctr, w_rank, w_action, w_attr, has_winner
    )
    return written, during, changed, vis, final_vis


def apply_op_patched(state: DocState, op: jax.Array, ranks: jax.Array, multi: jax.Array):
    """Faithful per-op application + a fixed-shape patch record.

    The record feeds host-side patch assembly (universe.assemble_patches),
    which produces the exact reference Patch stream (micromerge.ts:25-30).
    """
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length
    visible = live & ~state.deleted
    kind = jnp.clip(op[K_KIND], 0, 3)
    is_insert = kind == KIND_INSERT
    is_delete = kind == KIND_DELETE
    is_mark = kind == KIND_MARK

    # Insert: visible position + inherited marks (pre-insert closest defined
    # boundary strictly left of the insertion gap; getActiveMarksAtIndex,
    # peritext.ts:328-330).
    t, _, _ = _rga_insert_position(state.elem_ctr, state.elem_act, state.length, op, ranks)
    ins_index = jnp.sum(visible & (ar < t)).astype(jnp.int32)
    slots = jnp.arange(2 * c, dtype=jnp.int32)
    defined = state.bnd_def & (slots < 2 * state.length)
    src_left = jnp.max(jnp.where(defined & (slots < 2 * t), slots, jnp.int32(-1)))
    ins_mask = jnp.where(
        src_left >= 0,
        lax.dynamic_slice_in_dim(state.bnd_mask, jnp.maximum(src_left, 0), 1, axis=0)[0],
        jnp.uint32(0),
    )

    # Delete: visible position of the target; valid only if not tombstoned.
    d_match = live & (state.elem_ctr == op[K_REF_CTR]) & (state.elem_act == op[K_REF_ACT])
    d_idx = jnp.argmax(d_match).astype(jnp.int32)
    del_valid = jnp.any(d_match) & ~state.deleted[d_idx]
    del_index = jnp.sum(visible & (ar < d_idx)).astype(jnp.int32)

    written, during, changed, vis, final_vis = _mark_patch_signals(state, op, ranks, multi)

    record = {
        "kind": kind,
        "index": jnp.where(is_insert, ins_index, del_index),
        "valid": is_insert | (is_delete & del_valid) | is_mark,
        "char": op[K_PAYLOAD],
        "obj_len": final_vis,
        "ins_mask": ins_mask,
        "written": written & is_mark,
        "during": during & is_mark,
        "changed": changed & is_mark,
        "vis": vis,
    }
    new_state = apply_op(state, op, ranks)
    return new_state, record


def _first_k_set(mask, k: int):
    """Indices of the first ``k`` set positions of a [N] bool vector, in
    ascending order: one cumsum + ``k`` binary searches (the running count
    is non-decreasing, so the first position where it reaches j+1 IS the
    j-th set position).  Scatter-free AND sort-free — lax.top_k lowers to
    a per-row partial sort that measures ~1.3 s at the bench record shape
    on CPU; this formulation is two orders of magnitude cheaper and
    equally TPU-friendly.  Returns (idx [k] i32 clamped into range,
    ok [k] bool, total i32 — the full set-bit count, for overflow
    guards)."""
    n = mask.shape[0]
    cs = jnp.cumsum(mask.astype(jnp.int32))
    q = jnp.arange(1, k + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(cs, q, side="left")
    ok = q <= cs[n - 1]
    return jnp.minimum(idx, n - 1).astype(jnp.int32), ok, cs[n - 1]


def compact_mark_records(
    written, during, changed, vis, obj_len, cand_def, span_cap: int, cand_cap: int
):
    """Device-side compaction of per-slot mark patch planes into run tables.

    The host's ``_mark_patch_list`` walk consumes the ``[M, 2C]`` planes
    only through their *emitted spans*: a patch opens at every written
    DURING slot whose effective marks change, spans to the next written
    slot's visibleIndex (or objLength), and survives the finishPartialPatch
    filters (peritext.ts:269-281).  All of that is a closed per-slot
    predicate, so the spans compact on device and the D2H readback becomes
    output-proportional: ``[M, span_cap]`` run tables instead of four
    ``[M, 2C]`` planes (ISSUE 8 tentpole; the event stream must be
    proportional to the edits, not the document — eg-walker/Collabs make
    the same argument host-side).

    Cost structure: every written slot is DEFINED in the post-batch
    boundary plane (anchor writes define their slots; in-range writes
    require definedness), and defined slots number at most 2x the mark
    table — the host-census bound behind the static ``cand_cap``.  So the
    2C axis is left once per replica (``_first_k_set`` over ``cand_def``,
    one [2C] cumsum shared by ALL mark rows) and everything per-row runs
    on the tiny compacted [M, cand_cap] candidate axis: gathers, one
    cumsum, binary searches.  No full-width per-row passes at all.

    ``cand_def`` must be in the SAME slot coordinates as the record
    planes — true for the sorted patched merge, whose mark records live
    on final post-placement coordinates.  The interleaved scan's records
    are per-op-INSTANT (each splice shifts the slot axis), so it passes
    ``cand_def=None`` and the compaction runs full-width per row instead
    (two [M, 2C] cumsums) — costlier, but that path is the deep-batch
    fallback whose asymptotics are already one scan step per op.

    Returns ``(run_start [M, K] i32, run_end [M, K] i32, count [M] i32)``:
    lanes hold the row's open (written & during & changed) slots in slot
    order — the walk's emission order — with the finishPartialPatch
    filters applied per lane (a filtered lane reads ``end <= start``; the
    host skips it).  ``count`` is the TRUE open-slot count: the host
    compares it against ``span_cap`` and falls back to the planes readback
    on overflow, so the cap never silently truncates a patch stream.
    """
    m, two_c = written.shape
    if cand_def is None:
        # Instant-coordinate planes: the full slot axis is its own
        # (exact) candidate set.
        d = two_c
        cand_total = None
        w_c = written
        open_c = written & during & changed
        vis_c = vis
    else:
        d = min(cand_cap, two_c)
        cand_idx, cand_ok, cand_total = _first_k_set(cand_def, d)
        gi = jnp.broadcast_to(cand_idx[None, :], (m, d))
        w_c = jnp.take_along_axis(written, gi, axis=1) & cand_ok[None, :]
        open_c = (
            w_c
            & jnp.take_along_axis(during, gi, axis=1)
            & jnp.take_along_axis(changed, gi, axis=1)
        )
        vis_c = jnp.take_along_axis(vis, gi, axis=1)

    # First span_cap open candidates per row, ascending (candidates are
    # already in slot order).
    k = min(span_cap, d)
    cs_open = jnp.cumsum(open_c.astype(jnp.int32), axis=1)
    q = jnp.arange(1, k + 1, dtype=jnp.int32)
    sel = jax.vmap(lambda a: jnp.searchsorted(a, q, side="left"))(cs_open)
    lane_ok = q[None, :] <= cs_open[:, d - 1 :]
    sel_c = jnp.minimum(sel, d - 1)
    start = jnp.take_along_axis(vis_c, sel_c, axis=1)

    # Patch end: the next WRITTEN candidate after the selected slot (the
    # walk's closing boundary), else objLength.
    cs_w = jnp.cumsum(w_c.astype(jnp.int32), axis=1)
    wk = jnp.take_along_axis(cs_w, sel_c, axis=1)
    nxt = jax.vmap(lambda a, t: jnp.searchsorted(a, t, side="left"))(cs_w, wk + 1)
    has_nxt = nxt < d
    end_raw = jnp.where(
        has_nxt,
        jnp.take_along_axis(vis_c, jnp.minimum(nxt, d - 1), axis=1),
        obj_len[:, None],
    )

    # finishPartialPatch filters (peritext.ts:269-281), per lane: a
    # filtered lane stores (0, 0) so the host's end > start test skips it.
    ok = lane_ok & (end_raw > start) & (start < obj_len[:, None])
    run_start = jnp.where(ok, start, 0)
    run_end = jnp.where(ok, jnp.minimum(end_raw, obj_len[:, None]), 0)
    # Self-guard: the host-census bound makes a candidate overflow
    # impossible (defined slots <= 2x mark table), but if it ever broke,
    # spans beyond the candidate axis would silently drop — so report a
    # beyond-cap count instead and let the host's overflow fallback read
    # the planes.
    count = cs_open[:, d - 1]
    if cand_total is not None:
        count = jnp.where(
            cand_total > d, jnp.full((m,), span_cap + 1, jnp.int32), count
        )
    if span_cap > k:  # degenerate tiny-capacity shape: pad the lanes
        pad = ((0, 0), (0, span_cap - k))
        run_start = jnp.pad(run_start, pad)
        run_end = jnp.pad(run_end, pad)
    return run_start, run_end, count


def apply_ops_patched(
    state: DocState,
    ops: jax.Array,
    ranks: jax.Array,
    multi: jax.Array,
    readback: str = "planes",
    span_cap: int = 8,
):
    def step(s, op):
        return apply_op_patched(s, op, ranks, multi)

    new_state, rec = lax.scan(step, state, ops)
    if readback != "compact":
        return new_state, rec
    # Output-proportional readback: the mark planes compact to run tables
    # (cand_def=None — the interleaved records are per-op-INSTANT slot
    # coordinates, see compact_mark_records), and fields the host already
    # holds in the encoded op rows (kind, the insert payload, obj_len —
    # compact mark patches carry their own clamped ends) drop from the
    # record dict entirely.
    run_start, run_end, count = compact_mark_records(
        rec["written"], rec["during"], rec["changed"], rec["vis"],
        rec["obj_len"], None, span_cap, 0,
    )
    return new_state, {
        "index": rec["index"],
        "valid": rec["valid"],
        "ins_mask": rec["ins_mask"],
        "mstart": run_start,
        "mend": run_end,
        "mcount": count,
    }


@functools.lru_cache(maxsize=None)
def _apply_ops_patched_jit(readback: str, span_cap: int):
    return jax.jit(
        functools.partial(apply_ops_patched, readback=readback, span_cap=span_cap)
    )


def apply_ops_patched_jit(
    state, ops, ranks, multi, readback: str = "planes", span_cap: int = 8
):
    if readback == "planes":  # cap unused: keep ONE jit cache entry
        span_cap = 8
    return _apply_ops_patched_jit(readback, span_cap)(state, ops, ranks, multi)


@functools.lru_cache(maxsize=None)
def _apply_ops_patched_batch(readback: str, span_cap: int):
    return jax.jit(
        jax.vmap(
            functools.partial(
                apply_ops_patched, readback=readback, span_cap=span_cap
            ),
            in_axes=(0, 0, None, None),
        )
    )


def apply_ops_patched_batch(
    states, ops, ranks, multi, readback: str = "planes", span_cap: int = 8
):
    if readback == "planes":  # cap unused: keep ONE jit cache entry
        span_cap = 8
    return _apply_ops_patched_batch(readback, span_cap)(states, ops, ranks, multi)


# ---------------------------------------------------------------------------
# Fast merge path: kind-split two-phase application
# ---------------------------------------------------------------------------
#
# State-equivalence argument for reordering a causally-sorted op batch into
# (all inserts+deletes, in order) followed by (all mark ops, in order):
# a mark op writes only boundary sets, whose contents are keyed by stable
# element identity; an insert splices *undefined* boundary slots, so it
# neither reads nor changes any defined set, and a delete only flips a
# tombstone flag that mark application ignores for state purposes (the
# visible index matters only for patch emission, which this path does not
# do).  Hence mark<->text adjacent transpositions preserve the final state,
# and the two-phase order is reachable by such transpositions while keeping
# each kind's internal order.  Patch-faithful application uses the
# interleaved apply_ops path instead.


def _apply_text_op(carry, op, ranks, char_buf=None):
    """Insert/delete on the reduced text state (no boundary tables).

    carry = (elem_ctr, elem_act, deleted, chars, orig_idx, length).
    ``orig_idx`` tags each element with its pre-batch position (-1 for
    elements inserted by this batch) so the boundary tables can be permuted
    once at the end of the phase instead of shifted per insert.

    With ``char_buf`` given, KIND_INSERT_RUN rows apply a whole chained
    insert run (one input op's characters) in a single step.  Chains land
    contiguously in the RGA order: the first op takes the normal position
    (skip run included), and each subsequent op references the one before
    it, whose successor — whatever originally followed the insertion point —
    has a *smaller* id than the chain's first op (that is what ended the
    skip run), hence smaller than every later chain op, so no further
    skipping can occur.  Characters come from ``char_buf`` at
    K_PAYLOAD..K_PAYLOAD+K_RUN_LEN; element counters are K_CTR..K_CTR+len-1.
    """
    elem_ctr, elem_act, deleted, chars, orig_idx, length = carry
    ar = jnp.arange(elem_ctr.shape[0], dtype=jnp.int32)
    live = ar < length
    is_insert = op[K_KIND] == KIND_INSERT
    is_run = (op[K_KIND] == KIND_INSERT_RUN) if char_buf is not None else jnp.bool_(False)
    is_delete = op[K_KIND] == KIND_DELETE

    # Delete: tombstone the match.
    match = live & (elem_ctr == op[K_REF_CTR]) & (elem_act == op[K_REF_ACT])
    deleted_after_del = deleted | (match & is_delete)

    # Insert: shared position rule, then masked-shift splice of k elements
    # (k = 1 for plain inserts).
    k = jnp.where(is_run, op[K_RUN_LEN], jnp.int32(1))
    t, _, _ = _rga_insert_position(elem_ctr, elem_act, length, op, ranks)
    keep = ar < t
    block = (ar >= t) & (ar < t + k)
    offset = ar - t  # position within the inserted block where `block`

    if char_buf is not None:
        run_chars = lax.dynamic_slice_in_dim(
            char_buf, op[K_PAYLOAD] * is_run.astype(jnp.int32), MAX_RUN_LEN
        )
        block_chars = run_chars[jnp.clip(offset, 0, MAX_RUN_LEN - 1)]
        char_vals = jnp.where(is_run, block_chars, op[K_PAYLOAD])
    else:
        char_vals = op[K_PAYLOAD]

    def splice(arr, value):
        return jnp.where(keep, arr, jnp.where(block, value, jnp.roll(arr, k)))

    any_insert = is_insert | is_run
    new_carry = (
        jnp.where(any_insert, splice(elem_ctr, op[K_CTR] + offset), elem_ctr),
        jnp.where(any_insert, splice(elem_act, op[K_ACT]), elem_act),
        jnp.where(any_insert, splice(deleted_after_del, False), deleted_after_del),
        jnp.where(any_insert, splice(chars, char_vals), chars),
        jnp.where(any_insert, splice(orig_idx, jnp.int32(-1)), orig_idx),
        length + jnp.where(any_insert, k, 0),
    )
    return new_carry, None


def _slot_permutation(orig_idx):
    """Flat slot-axis form of a text phase's element permutation:
    ``(valid [2C], flat_src [2C])`` mapping each post-splice boundary slot
    to its pre-splice slot, for the paths that hold only a permutation
    after placement (the scan merge, the Pallas text phase, the
    sequence-sharded placement, the patch-emitting merge) and re-align
    their per-slot planes with a gather.  Deliberately flat: a [C, 2,
    ...]-view gather costs the compiler full-plane layout copies
    (PROFILE_r05.md).  XLA on the TPU runs such a gather close to one
    element at a time, which is why the full-table sorted merge moves its
    boundary tables with the placement's shift passes instead
    (_place_round)."""
    c = orig_idx.shape[0]
    slots = jnp.arange(2 * c, dtype=jnp.int32)
    valid = (orig_idx >= 0)[slots // 2]
    flat_src = 2 * jnp.maximum(orig_idx, 0)[slots // 2] + slots % 2
    return valid, flat_src


def _permute_boundaries(bnd_def, bnd_mask, orig_idx):
    """Re-align boundary tables after a text phase, in one gather."""
    valid, flat_src = _slot_permutation(orig_idx)
    new_def = jnp.where(valid, bnd_def[flat_src], False)
    new_mask = jnp.where(valid[:, None], bnd_mask[flat_src], jnp.uint32(0))
    return new_def, new_mask


def _apply_mark_fast(carry, op, elem_ctr, elem_act, length):
    """Mark application without patches, cummax, or full-width gathers.

    Only three kinds of slots are written (see _apply_mark's derivation):
    already-defined slots inside [start, end) OR in their own op bit (their
    carry is their own row); the start slot takes (nearest defined row at or
    left of it) | bit; the end slot takes its carry row unchanged.  The two
    carry lookups are single dynamic row reads.
    """
    bnd_def, bnd_mask, mark_ctr, mark_act, mark_action, mark_type, mark_attr, mark_count = carry
    c = elem_ctr.shape[0]
    is_mark = op[K_KIND] == KIND_MARK
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < length
    big = jnp.int32(2 * c + 2)

    s_match = live & (elem_ctr == op[K_SCTR]) & (elem_act == op[K_SACT])
    s_slot = 2 * jnp.argmax(s_match).astype(jnp.int32) + op[K_SKIND]
    e_match = live & (elem_ctr == op[K_ECTR]) & (elem_act == op[K_EACT])
    e_slot = jnp.where(
        op[K_EKIND] == 2,
        big,
        2 * jnp.argmax(e_match).astype(jnp.int32) + jnp.minimum(op[K_EKIND], 1),
    )
    # Same-slot anchors: start branch wins in the walk -> endOfText behavior
    # (see _mark_slot_context).
    e_slot = jnp.where(e_slot == s_slot, big, e_slot)

    slots = jnp.arange(2 * c, dtype=jnp.int32)
    defined = bnd_def & (slots < 2 * length)

    def carry_row_at(p):
        src = jnp.max(jnp.where(defined & (slots <= p), slots, jnp.int32(-1)))
        row = lax.dynamic_slice_in_dim(bnd_mask, jnp.maximum(src, 0), 1, axis=0)[0]
        return jnp.where(src >= 0, row, jnp.uint32(0))

    m = mark_count
    bit = jnp.uint32(1) << (m % MASK_WORD_BITS).astype(jnp.uint32)
    op_bit_row = jnp.zeros_like(bnd_mask[0]).at[m // MASK_WORD_BITS].set(bit)

    s_lt_e = s_slot < e_slot
    in_range = (slots >= s_slot) & (slots < e_slot) & s_lt_e & is_mark

    # Defined slots inside the range OR in the op bit.
    new_mask = jnp.where(
        (in_range & defined)[:, None], bnd_mask | op_bit_row[None, :], bnd_mask
    )
    # Start slot: carry | bit (single row update).
    row_s = (carry_row_at(s_slot) | op_bit_row)[None, :]
    write_s = is_mark & s_lt_e
    new_mask = jnp.where(
        write_s,
        lax.dynamic_update_slice_in_dim(new_mask, row_s, s_slot, axis=0),
        new_mask,
    )
    # End slot: plain carry row (no bit).  Skipped for endOfText.
    e_clamped = jnp.minimum(e_slot, jnp.int32(2 * c - 1))
    write_e = is_mark & (e_slot < 2 * c)
    row_e = carry_row_at(e_clamped)[None, :]
    new_mask = jnp.where(
        write_e,
        lax.dynamic_update_slice_in_dim(new_mask, row_e, e_clamped, axis=0),
        new_mask,
    )
    new_def = bnd_def | (in_range & defined) | ((slots == s_slot) & write_s) | (
        (slots == e_slot) & write_e
    )

    new_carry = (
        new_def,
        new_mask,
        jnp.where(is_mark, mark_ctr.at[m].set(op[K_CTR]), mark_ctr),
        jnp.where(is_mark, mark_act.at[m].set(op[K_ACT]), mark_act),
        jnp.where(is_mark, mark_action.at[m].set(op[K_MACTION]), mark_action),
        jnp.where(is_mark, mark_type.at[m].set(op[K_MTYPE]), mark_type),
        jnp.where(is_mark, mark_attr.at[m].set(op[K_MATTR]), mark_attr),
        m + is_mark.astype(jnp.int32),
    )
    return new_carry, None


def merge_step(
    state: DocState,
    text_ops: jax.Array,
    mark_ops: jax.Array,
    ranks: jax.Array,
    char_buf: jax.Array | None = None,
) -> DocState:
    """Fast batched merge: text phase -> boundary permute -> mark phase.

    The production remote-ingestion path (no patch emission).  ``text_ops``
    holds the batch's inserts/deletes in causal order, ``mark_ops`` its mark
    ops in causal order; both padded with KIND_PAD rows.  With ``char_buf``,
    text rows may be fused KIND_INSERT_RUN rows (encode.fuse_insert_runs),
    applying a whole typing run per scan step.
    """
    c = state.capacity
    orig_idx = jnp.arange(c, dtype=jnp.int32)

    text_carry = (state.elem_ctr, state.elem_act, state.deleted, state.chars, orig_idx, state.length)
    (elem_ctr, elem_act, deleted, chars, orig_idx, length), _ = lax.scan(
        lambda cry, op: _apply_text_op(cry, op, ranks, char_buf), text_carry, text_ops
    )
    bnd_def, bnd_mask = _permute_boundaries(state.bnd_def, state.bnd_mask, orig_idx)

    mark_carry = (
        bnd_def,
        bnd_mask,
        state.mark_ctr,
        state.mark_act,
        state.mark_action,
        state.mark_type,
        state.mark_attr,
        state.mark_count,
    )
    (bnd_def, bnd_mask, mark_ctr, mark_act, mark_action, mark_type, mark_attr, mark_count), _ = lax.scan(
        lambda cry, op: _apply_mark_fast(cry, op, elem_ctr, elem_act, length),
        mark_carry,
        mark_ops,
    )

    return DocState(
        elem_ctr=elem_ctr,
        elem_act=elem_act,
        deleted=deleted,
        chars=chars,
        bnd_def=bnd_def,
        bnd_mask=bnd_mask,
        mark_ctr=mark_ctr,
        mark_act=mark_act,
        mark_action=mark_action,
        mark_type=mark_type,
        mark_attr=mark_attr,
        length=length,
        mark_count=mark_count,
    )


merge_step_vmapped = jax.vmap(merge_step, in_axes=(0, 0, 0, None))
merge_step_batch = jax.jit(merge_step_vmapped)
merge_step_fused_vmapped = jax.vmap(merge_step, in_axes=(0, 0, 0, None, 0))
merge_step_fused_batch = jax.jit(merge_step_fused_vmapped)


# ---------------------------------------------------------------------------
# Sort-based batch integration: place a whole op batch in O(depth) rounds
# ---------------------------------------------------------------------------
#
# The scan paths above keep the reference's asymptotics — L ops cost L
# sequential O(C) steps.  This path integrates an entire causally-ordered
# text-op batch in D vectorized rounds, where D is the batch's reference
# *depth* (how many ops chain through elements created earlier in the same
# batch — computed on host by encode.compute_rounds; 1 for fully concurrent
# batches, small in practice because insert runs are pre-fused).
#
# Correctness (simultaneous placement == sequential application): for ops
# whose references all pre-exist the round, sequential RGA application in any
# causal order equals a stable merge keyed by (t, descending op id), where
# t(op) = min{ j > idx(ref) : ~(alive_j & id_j > id_op) } is the op's skip-run
# stop (micromerge.ts:630-635) computed against the *pre-round* array:
# - same t, any refs: the later-applied op's scan stops exactly at (greater
#   id) or immediately before (smaller id) the earlier op's block, which is
#   the descending-id order.
# - different t: an op B can only encounter a previously placed block A
#   inside its skip run when every pre-round element between them exceeds
#   B's id; A's own stop rule then forces t_A >= t_B unless id_A > id_B, so
#   a smaller-id block can never land strictly inside B's run — B's stop
#   element (hence its placement) is unchanged by A, and positions shift by
#   exactly the blocks placed at or before them.
# Ops whose reference is created by the batch itself go in a later round
# (their reference then pre-exists), and rounds respect causal order, so the
# round decomposition is a causal-order-preserving reordering — which
# preserves the final state exactly as the two-phase argument above.
# Deletes never affect placement (the stop rule reads allocation, not
# tombstones) and apply as one [L, C] masked match per round.


# Splice by shifts.  XLA on the TPU lowers a batched gather (and a generic
# scatter) with per-element indices close to one element at a time: on a
# v5e a [5120, 2048] plane gathered by a sort permutation moved ~86 M
# elements/s, ~0.3 GB/s against 819 GB/s of HBM.  Placement moves every
# existing element right by a displacement that never decreases along the
# array (``shifts`` counts the blocks placed at or before it), so the
# output needs no per-element index at all: MSB-first binary-decomposed
# static rolls, each fused with a select, move the elements (and every
# per-slot plane that rides with them, at twice the displacement), and the
# op blocks fill in from small one-hot reductions over the round's ops.
# Each pass is a static lane shift plus a select, which the TPU runs at
# vector and HBM speed.


def _place_round(carry, r, ops, round_of, ranks, char_buf, maxk: int):
    """Apply every round-r text op simultaneously.

    ``carry`` is (elem_ctr, elem_act, deleted, chars, orig_idx, length,
    slot_planes): ``slot_planes`` holds per-boundary-slot planes ([2C] or
    [2C, W], slot 2i and 2i+1 belonging to element i) that move with their
    elements; the slots of inserted and beyond-length elements come out
    False / 0.
    """
    elem_ctr, elem_act, deleted, chars, orig_idx, length, slot_planes = carry
    c = elem_ctr.shape[0]
    ar = jnp.arange(c, dtype=jnp.int32)
    alive = ar < length

    kind = ops[:, K_KIND]
    active = round_of == r
    is_ins = active & ((kind == KIND_INSERT) | (kind == KIND_INSERT_RUN))
    is_run = kind == KIND_INSERT_RUN
    is_del = active & (kind == KIND_DELETE)

    ref_ctr = ops[:, K_REF_CTR]
    ref_act = ops[:, K_REF_ACT]
    ref_match = (
        alive[None, :]
        & (elem_ctr[None, :] == ref_ctr[:, None])
        & (elem_act[None, :] == ref_act[:, None])
    )  # [L, C]

    # Deletes: tombstone every match in one pass.
    deleted = deleted | (ref_match & is_del[:, None]).any(axis=0)

    # Insert placement: the shared skip-run stop rule, batched over ops.
    ctr_i = ops[:, K_CTR]
    rank_i = ranks[ops[:, K_ACT]]
    is_head = (ref_ctr == 0) & (ref_act == 0)
    idx = jnp.where(is_head, jnp.int32(-1), jnp.argmax(ref_match, axis=1).astype(jnp.int32))
    elem_rank = ranks[elem_act]
    gt = (elem_ctr[None, :] > ctr_i[:, None]) | (
        (elem_ctr[None, :] == ctr_i[:, None]) & (elem_rank[None, :] > rank_i[:, None])
    )  # [L, C]
    stop = (ar[None, :] > idx[:, None]) & ~(alive[None, :] & gt)
    t = jnp.min(jnp.where(stop, ar[None, :], c), axis=1).astype(jnp.int32)  # [L]

    k = jnp.where(is_run, ops[:, K_RUN_LEN], 1) * is_ins.astype(jnp.int32)  # [L]

    # Final block starts: stable order (t, descending op id) among the
    # round's inserts; inactive ops contribute k = 0.
    id_gt = (ctr_i[None, :] > ctr_i[:, None]) | (
        (ctr_i[None, :] == ctr_i[:, None]) & (rank_i[None, :] > rank_i[:, None])
    )  # [L, L]: op j's id > op i's id
    before = (t[None, :] < t[:, None]) | ((t[None, :] == t[:, None]) & id_gt)
    s = t + jnp.sum(k[None, :] * before.astype(jnp.int32), axis=1)  # [L]

    # Existing elements shift right by every block placed at or before them.
    shifts = jnp.sum(k[:, None] * (t[:, None] <= ar[None, :]).astype(jnp.int32), axis=0)

    def per_slot(x):  # [C] -> [2C]: repeat per slot pair, no indexing
        return jnp.broadcast_to(x[:, None], (c, 2)).reshape(2 * c)

    def lift(cond, plane):  # broadcast a [2C] condition over a slot plane
        return cond.reshape(cond.shape + (1,) * (plane.ndim - 1))

    # Move existing elements right by their displacement: one static roll +
    # select per bit, MSB first.
    #
    # Correctness of the greedy bit decomposition: displacements are
    # non-decreasing along positions (shifts is a cumulative count), and
    # MSB-first keeps every alive remainder below the current step's
    # doubled width; if a mover (rem >= step) could land on an alive
    # slower value (0 < rem < step), the two remainders mod 2*step
    # would have to differ by more than the values' destination gap
    # allows — impossible for monotone displacements (proof by the
    # mod-2^(b+1) window: rem_X >= 2^b, delta <= 2^b - 2 forces
    # rem_Y = rem_X + delta mod 2^(b+1) >= 2^b, a contradiction).
    # Stale source copies are marked DEAD (-1) so they never move again;
    # every position below the new length is re-covered by a mover, a
    # block lane, or its unmoved occupant, and the tail is masked to the
    # fills.  Slot planes take the same selects at twice the shift.  Bool
    # planes ride the passes as int32: on the CPU backend a rolled pred
    # plane adds conversion kernels to every pass.
    rem = shifts  # [C]; beyond-length lanes inherit the running count
    planes = [elem_ctr, elem_act, deleted.astype(jnp.int32), chars, orig_idx]
    slot_dtypes = [p.dtype for p in slot_planes]
    slot_planes = [
        p.astype(jnp.int32) if p.dtype == jnp.bool_ else p for p in slot_planes
    ]
    max_disp = ops.shape[0] * maxk
    for b in reversed(range(max_disp.bit_length())):
        step = 1 << b
        moved_rem = jnp.roll(rem, step)
        sel = (moved_rem >= step) & (ar >= step)  # block wraparound
        planes = [jnp.where(sel, jnp.roll(p, step), p) for p in planes]
        sel2 = per_slot(sel)
        slot_planes = [
            jnp.where(lift(sel2, p), jnp.roll(p, 2 * step, axis=0), p)
            for p in slot_planes
        ]
        rem = jnp.where(sel, moved_rem - step, jnp.where(rem >= step, -1, rem))

    # Block fill: each output position belongs to at most one op block
    # (destinations are unique), so masked int max-reductions are exact.
    in_blk = (
        is_ins[:, None]
        & (ar[None, :] >= s[:, None])
        & (ar[None, :] < (s + k)[:, None])
    )  # [L, C]
    neg = jnp.int32(-(2**31) + 1)
    blk_any = in_blk.any(axis=0)

    def from_ops(vals_l):  # [L] -> [C] masked max over the owning op
        return jnp.max(jnp.where(in_blk, vals_l[:, None], neg), axis=0)

    blk_ctr = from_ops(ctr_i - s) + ar  # ctr_l + (p - s_l)
    blk_act = from_ops(ops[:, K_ACT])
    # Run chars via the [L*maxk] block-lane one-hot (payload chars for
    # plain inserts ride the same table).
    off = jnp.arange(maxk, dtype=jnp.int32)
    in_block = (off[None, :] < k[:, None]) & is_ins[:, None]
    dest_ops = jnp.where(in_block, s[:, None] + off[None, :], c)
    buf_idx = jnp.clip(ops[:, K_PAYLOAD, None] + off[None, :], 0, char_buf.shape[0] - 1)
    block_chars = jnp.where(
        is_run[:, None], char_buf[buf_idx], ops[:, K_PAYLOAD, None]
    )
    lane_hit = (
        in_block.reshape(-1)[:, None]
        & (dest_ops.reshape(-1)[:, None] == ar[None, :])
    )  # [L*maxk, C]
    blk_char = jnp.max(
        jnp.where(lane_hit, block_chars.reshape(-1)[:, None], neg), axis=0
    )

    new_length = length + jnp.sum(k)
    live_out = ar < new_length
    outs = [
        jnp.where(blk_any, blk_ctr, planes[0]),
        jnp.where(blk_any, blk_act, planes[1]),
        jnp.where(blk_any, 0, planes[2]),
        jnp.where(blk_any, blk_char, planes[3]),
        jnp.where(blk_any, -1, planes[4]),
    ]
    fills = (0, 0, 0, 0, -1)
    outs = [jnp.where(live_out, o, f) for o, f in zip(outs, fills)]
    outs[2] = outs[2].astype(bool)
    slot_kept = per_slot(live_out & ~blk_any)
    slot_planes = tuple(
        jnp.where(lift(slot_kept, p), p, jnp.zeros((), p.dtype)).astype(dt)
        for p, dt in zip(slot_planes, slot_dtypes)
    )
    return (*outs, new_length, slot_planes)


def place_text_batch(
    elem_ctr, elem_act, deleted, chars, length, text_ops, round_of, num_rounds,
    ranks, char_buf, maxk: int, slot_planes=(),
):
    """Integrate a causally-ordered text-op batch in ``num_rounds`` rounds.

    Returns ``(elem_ctr, elem_act, deleted, chars, orig_idx, length,
    *slot_planes)``: the updated element arrays, the orig-index permutation
    plane (each element's pre-batch position, -1 for batch-born ones), and
    the given per-slot planes (the boundary tables) moved with their
    elements.  ``num_rounds`` is a traced scalar — one compiled program
    serves any batch depth.
    """
    c = elem_ctr.shape[0]
    carry = (
        elem_ctr, elem_act, deleted, chars, jnp.arange(c, dtype=jnp.int32), length,
        tuple(slot_planes),
    )
    carry = lax.fori_loop(
        0,
        num_rounds,
        lambda r, cry: _place_round(cry, r, text_ops, round_of, ranks, char_buf, maxk),
        carry,
    )
    return carry[:-1] + carry[-1]


# Batched mark application.  Sequential dependence between mark ops comes
# only from two channels: (1) an op's start/end writes *define* slots that
# later ops' carry lookups can select, and (2) an op's written row becomes
# the base that later in-range ops OR their bit into.  Both channels have a
# closed form over the whole batch:
#
#   final_row(p) = base(p) | OR{ bit_j : j > last_rebase(p), s_j < p < e_j }
#
# where last_rebase(p) is the last op writing p via its start/end slot, and
# base(p) is the row that op wrote — its carry source's row *frozen at that
# time*, which expands recursively through (slot, time) parent links.  The
# recursion is resolved with pointer doubling over the 2M write-nodes
# (S-node = the row op m writes at its start slot, E-node = at its end
# slot): each node's accumulated value ORs its own contribution (bit +
# in-range bits between its parent's time and its own) with its parent
# chain's.  log2(2M) gather rounds replace the M sequential scan steps.


def _batched_anchor_slots(mark_ops, elem_ctr, elem_act, length):
    """Anchor-slot resolution for a whole mark batch at once.

    Same rules as _mark_slot_context / _apply_mark_fast — including the
    same-slot -> endOfText walk-order subtlety (peritext.ts:236-241) —
    batched over the op axis.  Anchors resolve against the *final* element
    plane and are time-independent, so every batch consumer (the batched
    mark phase, the first-definition timeline, and the compact-delta patch
    scan) shares this one definition.  Returns ``(valid, s_slot, e_slot)``
    with e_slot already remapped to the beyond-any-slot sentinel for
    endOfText and same-slot anchors.
    """
    c = elem_ctr.shape[0]
    big = jnp.int32(2 * c + 2)
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < length

    valid = mark_ops[:, K_KIND] == KIND_MARK
    s_match = (
        live[None, :]
        & (elem_ctr[None, :] == mark_ops[:, K_SCTR, None])
        & (elem_act[None, :] == mark_ops[:, K_SACT, None])
    )
    s_slot = 2 * jnp.argmax(s_match, axis=1).astype(jnp.int32) + mark_ops[:, K_SKIND]
    e_match = (
        live[None, :]
        & (elem_ctr[None, :] == mark_ops[:, K_ECTR, None])
        & (elem_act[None, :] == mark_ops[:, K_EACT, None])
    )
    e_slot = jnp.where(
        mark_ops[:, K_EKIND] == 2,
        big,
        2 * jnp.argmax(e_match, axis=1).astype(jnp.int32)
        + jnp.minimum(mark_ops[:, K_EKIND], 1),
    )
    e_slot = jnp.where(e_slot == s_slot, big, e_slot)  # same-slot -> endOfText
    return valid, s_slot, e_slot


def _or_accumulate(mask: jax.Array, bit_rows: jax.Array) -> jax.Array:
    """OR of the selected one-hot bit rows: [N, M] bool x [M, W] uint32.

    Every row of ``bit_rows`` carries a *distinct* bit, so a sum has no
    carries and equals the OR — but a float32 matmul can only be trusted up
    to the 24-bit mantissa.  Split each word into 16-bit halves first: every
    column then sums distinct powers of two below 2^16, exact in float32,
    and the accumulation runs as one MXU-shaped [N, M] x [M, 2W] matmul
    instead of materializing an [N, M, W] integer intermediate.
    """
    sel = mask.astype(jnp.float32)
    lo = (bit_rows & jnp.uint32(0xFFFF)).astype(jnp.float32)
    hi = (bit_rows >> 16).astype(jnp.float32)
    out = sel @ jnp.concatenate([lo, hi], axis=1)  # [N, 2W], exact
    w = bit_rows.shape[1]
    return out[:, :w].astype(jnp.uint32) | (out[:, w:].astype(jnp.uint32) << 16)


def _apply_marks_batch(
    bnd_def, bnd_mask, mark_ops, elem_ctr, elem_act, length, mark_count, w_words,
):
    """Apply a causally-ordered mark-op batch to the boundary tables at once.

    The batch closed form of the reference's applyAddRemoveMark walk
    (peritext.ts:154-223) under the write-class derivation documented on
    _apply_mark: same anchor rules (including the same-slot -> endOfText
    walk-order subtlety, peritext.ts:236-241) and the same carried
    ``currentOps`` semantics, resolved for all ops simultaneously.
    Bit-exact with scanning _apply_mark_fast over the same rows (differential
    coverage in tests/test_sorted_merge.py).  Returns (bnd_def, bnd_mask).

    The tables come in already aligned with the element planes (the
    placement moves them with its shift passes), so every per-slot read is
    the plane itself: nothing here indexes a [2C] plane by a [2C] index.
    Lookups into the batch's small tables (per op, per write node) expand
    to slots as static select chains, not gathers.
    """
    m_ops = mark_ops.shape[0]
    c = elem_ctr.shape[0]
    two_c = 2 * c
    midx = jnp.arange(m_ops, dtype=jnp.int32)
    slots = jnp.arange(two_c, dtype=jnp.int32)

    # Anchor resolution (same rules as _apply_mark_fast, batched).
    valid, s_slot, e_slot = _batched_anchor_slots(mark_ops, elem_ctr, elem_act, length)

    # Bit rows: op m's table index is mark_count + (rank among valid rows).
    # The batch's new bits all land in a narrow WORD WINDOW of the [.., W]
    # plane — at most ceil(M/32)+1 words starting at mark_count//32 — so
    # every batch-bit tensor (B, segment ORs, the accumulation matmuls, the
    # tail plane) is built at window width w_act instead of the full table
    # width W.  At the bench shape (W=32, ~22 mark rows -> w_act=2) this
    # removes the dominant HBM traffic of the whole merge: the [2C, 2W] f32
    # accumulate plane and several full-width [2C, W] intermediates
    # (roofline_r05: see PROFILE notes).  Only the carry ROOT rows (pre-
    # batch rows, bits anywhere) stay full-width, and they ride the tiny
    # [2M, W] node table.
    mpos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    bit_idx = mark_count + mpos  # [M]
    w_act = min((m_ops + MASK_WORD_BITS - 1) // MASK_WORD_BITS + 1, w_words)
    w0 = jnp.clip(mark_count // MASK_WORD_BITS, 0, w_words - w_act)
    bit_off = bit_idx - w0 * MASK_WORD_BITS  # window-relative, in [0, 32*w_act)
    word_ar = jnp.arange(w_act, dtype=jnp.int32)
    B = jnp.where(
        valid[:, None] & (word_ar[None, :] == bit_off[:, None] // MASK_WORD_BITS),
        jnp.uint32(1) << (bit_off[:, None] % MASK_WORD_BITS).astype(jnp.uint32),
        jnp.uint32(0),
    )  # [M, w_act]

    d0 = bnd_def & (slots < 2 * length)  # defined before the batch

    writes_s = valid & (s_slot < e_slot)
    writes_e = valid & (e_slot < two_c)
    WS = writes_s[:, None] & (slots[None, :] == s_slot[:, None])  # [M, 2C]
    WE = writes_e[:, None] & (slots[None, :] == e_slot[:, None])
    w_any = WS | WE
    written_any = w_any.any(axis=0)  # [2C]
    ws_last = jnp.max(jnp.where(WS, midx[:, None], -1), axis=0)  # [2C]
    we_last = jnp.max(jnp.where(WE, midx[:, None], -1), axis=0)
    w_last = jnp.maximum(ws_last, we_last)
    f_first = jnp.min(jnp.where(w_any, midx[:, None], m_ops), axis=0)
    # First time each slot is defined: -1 = pre-batch, m_ops+1 = never.
    def_time = jnp.where(
        d0, jnp.int32(-1), jnp.where(written_any, f_first, jnp.int32(m_ops + 1))
    )

    in_range = (
        writes_s[:, None]
        & (slots[None, :] > s_slot[:, None])
        & (slots[None, :] < e_slot[:, None])
    )  # [M, 2C]
    in_range_t = in_range.T  # [2C, M]
    w_any_t = w_any.T

    def carry_node(p):  # p [M] target slots -> (q, prev, seg bits, root row)
        # Nearest slot defined before this op's turn.
        cand = (slots[None, :] <= p[:, None]) & (def_time[None, :] < midx[:, None])
        q = jnp.max(jnp.where(cand, slots[None, :], -1), axis=1)  # [M]
        qc = jnp.maximum(q, 0)
        # Last batch op writing q before this one (-1: q's row is pre-batch).
        wq = w_any_t[qc] & (q >= 0)[:, None]  # [M, M]
        prev_cand = wq & (midx[None, :] < midx[:, None])
        prev = jnp.max(jnp.where(prev_cand, midx[None, :], -1), axis=1)  # [M]
        # Bits ORed into q between prev and this op (in-range, defined).
        seg = in_range_t[qc] & (q >= 0)[:, None]
        seg = seg & (midx[None, :] > prev[:, None]) & (midx[None, :] < midx[:, None])
        seg_bits = _or_accumulate(seg, B)  # [M, w_act] window bits
        # Root base: q's pre-batch row when no batch op rebased it first
        # (full-width — pre-batch bits live anywhere in the table).
        root_row = jnp.where(
            ((prev < 0) & (q >= 0))[:, None] & d0[qc][:, None],
            bnd_mask[qc],
            jnp.uint32(0),
        )
        return q, prev, seg_bits, root_row

    q_s, prev_s, seg_s, root_s = carry_node(s_slot)
    e_clamped = jnp.minimum(e_slot, jnp.int32(two_c - 1))
    q_e, prev_e, seg_e, root_e = carry_node(e_clamped)

    # Node table: node m = op m's S-write row, node M+m = its E-write row.
    def parent_node(prev, q):
        # prev's S node if its start slot is q, else its E node.
        is_s = s_slot[jnp.maximum(prev, 0)] == q
        return jnp.where(prev < 0, -1, jnp.where(is_s, prev, prev + m_ops))

    # Split accumulation: window bits [2M, w_act] and full-width root rows
    # [2M, W] (each chain has exactly one root node carrying a nonzero
    # root_row; the OR-propagation delivers it to every chain member).
    acc_win = jnp.concatenate([seg_s | B, seg_e], axis=0)
    acc_root = jnp.concatenate([root_s, root_e], axis=0)
    ptr = jnp.concatenate([parent_node(prev_s, q_s), parent_node(prev_e, q_e)])

    # Pointer doubling: fold each node's ancestor chain into its value.
    n_nodes = 2 * m_ops
    steps = max(1, (n_nodes - 1).bit_length())
    for _ in range(steps):
        pc = jnp.maximum(ptr, 0)
        chained = (ptr >= 0)[:, None]
        acc_win = acc_win | jnp.where(chained, acc_win[pc], jnp.uint32(0))
        acc_root = acc_root | jnp.where(chained, acc_root[pc], jnp.uint32(0))
        ptr = jnp.where(ptr >= 0, ptr[pc], ptr)

    # Per-slot final rows.  Full-width pass: written slots are REBASED to
    # their writer's root row (replacing the old row in every word);
    # everything else keeps its old row.  Window pass: the batch's new bits
    # (rebase chain + tail) OR into the w_act active words only.
    # A slot's writer node: the last op writing it, as its S node when that
    # op wrote it as its start (an op never writes one slot as both).
    node_at = jnp.where(ws_last > we_last, ws_last, we_last + m_ops)
    written_col = written_any[:, None]
    # Expand the tiny [2M, .] node tables to written slots as static OR-
    # select chains instead of [2C]-index gathers: the chains stay inside
    # the one full-plane output fusion, where a gather would run close to
    # one element at a time and materialize its own [2C, .] plane.
    # Guarded: HLO size and trace time scale with the (padded) node count,
    # so unusually deep mark batches fall back to the gathers.
    if n_nodes <= 128:
        root_at = jnp.uint32(0)
        win_at = jnp.uint32(0)
        for n in range(n_nodes):
            hit = (node_at == n)[:, None]
            root_at = root_at | jnp.where(hit, acc_root[n], jnp.uint32(0))
            win_at = win_at | jnp.where(hit, acc_win[n], jnp.uint32(0))
    else:
        root_at = acc_root[node_at]
        win_at = acc_win[node_at]
    base_full = jnp.where(written_col, root_at, bnd_mask)
    start_time = jnp.where(written_any, w_last, -1)
    tail_mask = in_range_t & (midx[None, :] > start_time[:, None])  # [2C, M]
    tail_w = _or_accumulate(tail_mask, B)  # [2C, w_act]
    # Tail bits apply to written rows and to pre-defined rows only (the
    # walk never marks undefined slots) — the old full-width `touched`
    # gate, expressed per window word.
    delta = (
        jnp.where(written_col, win_at, jnp.uint32(0))
        | jnp.where(written_col | d0[:, None], tail_w, jnp.uint32(0))
    )  # [2C, w_act]
    # Scatter the window back over the word axis as w_act static broadcast-
    # selects (w_act is ~2) — pure elementwise, fuses into the single full-
    # plane output pass; a word-axis gather here lowers to an extra
    # W-major plane materialization.
    word_full = jnp.arange(w_words, dtype=jnp.int32)
    expanded = jnp.uint32(0)
    for j in range(w_act):
        expanded = expanded | jnp.where(
            word_full[None, :] == w0 + j, delta[:, j][:, None], jnp.uint32(0)
        )
    new_mask = base_full | expanded
    new_def = bnd_def | written_any
    return new_def, new_mask


def _append_mark_table(state_fields, mark_ops, mark_count, m_cap):
    """Scatter-append a mark batch's rows into the per-replica mark table."""
    mark_ctr, mark_act, mark_action, mark_type, mark_attr = state_fields
    valid = mark_ops[:, K_KIND] == KIND_MARK
    idx = mark_count + jnp.cumsum(valid.astype(jnp.int32)) - 1
    safe = jnp.where(valid, idx, m_cap)

    def scat(col, field):
        return col.at[safe].set(mark_ops[:, field], mode="drop")

    return (
        scat(mark_ctr, K_CTR),
        scat(mark_act, K_ACT),
        scat(mark_action, K_MACTION),
        scat(mark_type, K_MTYPE),
        scat(mark_attr, K_MATTR),
        mark_count + valid.sum().astype(jnp.int32),
    )


def _sorted_tail(
    state: DocState, elem_ctr, elem_act, deleted, chars, length, bnd_def, bnd_mask,
    mark_ops,
) -> DocState:
    """Post-placement tail shared by the sorted merges: batched mark phase
    over boundary tables already aligned with the placed elements, plus the
    table append, per replica."""
    bnd_def, bnd_mask = _apply_marks_batch(
        bnd_def,
        bnd_mask,
        mark_ops,
        elem_ctr,
        elem_act,
        length,
        state.mark_count,
        state.bnd_mask.shape[-1],
    )
    mark_ctr, mark_act, mark_action, mark_type, mark_attr, mark_count = _append_mark_table(
        (state.mark_ctr, state.mark_act, state.mark_action, state.mark_type, state.mark_attr),
        mark_ops,
        state.mark_count,
        state.max_mark_ops,
    )
    return DocState(
        elem_ctr=elem_ctr,
        elem_act=elem_act,
        deleted=deleted,
        chars=chars,
        bnd_def=bnd_def,
        bnd_mask=bnd_mask,
        mark_ctr=mark_ctr,
        mark_act=mark_act,
        mark_action=mark_action,
        mark_type=mark_type,
        mark_attr=mark_attr,
        length=length,
        mark_count=mark_count,
    )


def merge_step_sorted(
    state: DocState,
    text_ops: jax.Array,
    round_of: jax.Array,
    num_rounds: jax.Array,
    mark_ops: jax.Array,
    ranks: jax.Array,
    char_buf: jax.Array,
    maxk: int,
) -> DocState:
    """Batched merge, both phases vectorized over the whole op batch.

    State-equivalent to merge_step (same two-phase argument); the text phase
    costs O(depth) vectorized rounds instead of O(#text ops) scan steps, and
    the mark phase costs O(log #marks) gather rounds instead of one scan
    step per mark op.
    """
    elem_ctr, elem_act, deleted, chars, _, length, bnd_def, bnd_mask = place_text_batch(
        state.elem_ctr,
        state.elem_act,
        state.deleted,
        state.chars,
        state.length,
        text_ops,
        round_of,
        num_rounds,
        ranks,
        char_buf,
        maxk,
        slot_planes=(state.bnd_def, state.bnd_mask),
    )
    return _sorted_tail(
        state, elem_ctr, elem_act, deleted, chars, length, bnd_def, bnd_mask, mark_ops
    )


@functools.lru_cache(maxsize=None)
def _merge_step_sorted_batch(maxk: int):
    return jax.jit(
        jax.vmap(
            functools.partial(merge_step_sorted, maxk=maxk),
            in_axes=(0, 0, 0, None, 0, None, 0),
        )
    )


def merge_step_sorted_batch(
    states,
    text_ops,
    round_of,
    num_rounds,
    mark_ops,
    ranks,
    char_buf,
    maxk: int,
    chunk: int | None = None,
):
    """Jitted batched entry point; one cache entry per maxk bucket.

    ``chunk`` (or PERITEXT_SORTED_CHUNK) is an opt-in memory valve: the
    placement/mark phases hold O(L*C + M*2C) transients *per replica*, so a
    very large unsharded batch can exceed HBM; chunking launches the same
    program over R-slices sequentially (at most two program shapes: the
    even chunks and one remainder).  Off by default — mesh-sharded batches
    already divide the transients across chips.
    """
    import os

    r = text_ops.shape[0]
    if chunk is None:
        chunk = int(os.environ.get("PERITEXT_SORTED_CHUNK", "0"))
    fn = _merge_step_sorted_batch(maxk)
    nr = jnp.int32(num_rounds)
    if not chunk or chunk >= r:
        return fn(states, text_ops, round_of, nr, mark_ops, ranks, char_buf)
    outs = []
    for i in range(0, r, chunk):
        sl = slice(i, min(i + chunk, r))
        outs.append(
            fn(
                jax.tree.map(lambda x: x[sl], states),
                text_ops[sl],
                round_of[sl],
                nr,
                mark_ops[sl],
                ranks,
                char_buf[sl],
            )
        )
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)


# ---------------------------------------------------------------------------
# Patch-emitting sorted merge: analytic text records + mark-only scan
# ---------------------------------------------------------------------------
#
# The faithful patch stream is a deterministic function of (pre-batch state,
# delivery-ordered op list).  The sorted placement gives every element's
# FINAL position up front; each op's patch context is then reconstructed
# from a timeline in final coordinates: born[p] / died[p] are the
# batch-stream instants the element at final position p appeared / was
# first tombstoned (+/-_TIME_BIG for pre-batch facts), so "visible at op
# i's instant" is the closed predicate born < t_i < died.  Insert/delete
# patch records (visible index, validity, inherited-mark source slot)
# become vectorized counting over that predicate — no per-op scan.  Mark
# ops still scan (their patch signals read evolving boundary sets), but
# only over the batch's MARK rows, on final coordinates with per-step time
# masks; a text-dominated batch (typing) no longer pays one sequential
# step per character.  Delivery-order fidelity needs run fusion gated on
# stream adjacency (encode.fuse_insert_runs with ``pos``): a fused run is
# modeled as k consecutive instants, which is exactly true only when no
# other op interleaves the chars in the delivery stream.

_TIME_BIG = 1 << 30


def _sorted_text_records(
    elem_ctr, elem_act, orig_idx, length, pre_deleted0,
    text_ops, text_time, mark_time, mark_valid,
):
    """Per-text-row patch records from the final placement + timeline.

    Returns (born, died, q, index0, tvalid, tm) where born/died are the
    [C] timeline arrays, q is each row's target's final position, index0
    the reference walk's visibleIndex at the row's instant
    (micromerge.ts:659 for inserts / 677-699 for deletes), tvalid the
    delete-idempotence validity, and tm the count of mark ops applied
    before the row's instant (its boundary-plane version).
    """
    c = elem_ctr.shape[0]
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < length
    pre = orig_idx >= 0
    pre_del = pre & pre_deleted0[jnp.maximum(orig_idx, 0)]

    kind = text_ops[:, K_KIND]
    is_ins = (kind == KIND_INSERT) | (kind == KIND_INSERT_RUN)
    is_del = kind == KIND_DELETE
    is_run = kind == KIND_INSERT_RUN
    ctr_l = text_ops[:, K_CTR]
    act_l = text_ops[:, K_ACT]
    k = jnp.where(is_run, text_ops[:, K_RUN_LEN], 1) * is_ins.astype(jnp.int32)
    t = text_time

    # born[p]: each batch-born element matches exactly one insert row; char
    # j of a run appeared at instant t + j (delivery-adjacent by fusion
    # gating).  Pre-batch elements: -BIG.
    created = (
        is_ins[:, None]
        & (elem_act[None, :] == act_l[:, None])
        & (elem_ctr[None, :] >= ctr_l[:, None])
        & (elem_ctr[None, :] < (ctr_l + k)[:, None])
    )  # [L, C]
    born_batch = jnp.sum(
        jnp.where(created, t[:, None] + (elem_ctr[None, :] - ctr_l[:, None]), 0),
        axis=0,
    )
    in_batch = created.any(axis=0)
    born = jnp.where(
        pre | ~in_batch, jnp.int32(-_TIME_BIG), born_batch.astype(jnp.int32)
    )

    # died[p]: first tombstoning instant (idempotent deletes: min).
    del_match = (
        is_del[:, None]
        & (elem_ctr[None, :] == text_ops[:, K_REF_CTR, None])
        & (elem_act[None, :] == text_ops[:, K_REF_ACT, None])
    )
    died_batch = jnp.min(
        jnp.where(del_match, t[:, None], jnp.int32(_TIME_BIG)), axis=0
    )
    died = jnp.where(pre_del, jnp.int32(-_TIME_BIG), died_batch)

    # Each row's target element's final position.
    tgt_ctr = jnp.where(is_del, text_ops[:, K_REF_CTR], ctr_l)
    tgt_act = jnp.where(is_del, text_ops[:, K_REF_ACT], act_l)
    tmatch = (
        live[None, :]
        & (elem_ctr[None, :] == tgt_ctr[:, None])
        & (elem_act[None, :] == tgt_act[:, None])
    )
    exists = jnp.any(tmatch, axis=1)
    q = jnp.argmax(tmatch, axis=1).astype(jnp.int32)

    # visibleIndex at the row's instant: elements final-ordered before the
    # target that had appeared and not yet been tombstoned.  (Relative
    # order of coexisting elements never changes, so final-order counting
    # equals the walk's position at that time.)
    alive = live[None, :] & (born[None, :] < t[:, None]) & (died[None, :] > t[:, None])
    index0 = jnp.sum(alive & (ar[None, :] < q[:, None]), axis=1).astype(jnp.int32)

    tvalid = jnp.where(is_del, exists & (born[q] < t) & (died[q] == t), is_ins)
    tm = jnp.sum(
        mark_valid[None, :] & (mark_time[None, :] < t[:, None]), axis=1
    ).astype(jnp.int32)
    return born, died, q, index0, tvalid, tm


def _sorted_def_first(bnd_def0, mark_ops, elem_ctr, elem_act, length):
    """First-definition mark index per boundary slot: -1 for pre-batch
    defined slots, else the first mark row anchoring (start/end-writing) the
    slot, else a sentinel beyond every instant.  Interior in-range writes
    can never *first*-define a slot (they require it defined already,
    peritext.ts:243-247), so anchor writes are the whole story — and anchor
    resolution is time-independent, making this fully analytic."""
    m_ops = mark_ops.shape[0]
    c = elem_ctr.shape[0]
    two_c = 2 * c
    midx = jnp.arange(m_ops, dtype=jnp.int32)
    slots = jnp.arange(two_c, dtype=jnp.int32)

    valid, s_slot, e_slot = _batched_anchor_slots(mark_ops, elem_ctr, elem_act, length)

    WS = (valid & (s_slot < e_slot))[:, None] & (slots[None, :] == s_slot[:, None])
    WE = (valid & (e_slot < two_c))[:, None] & (slots[None, :] == e_slot[:, None])
    first = jnp.min(jnp.where(WS | WE, midx[:, None], jnp.int32(m_ops + 1)), axis=0)
    return jnp.where(bnd_def0, jnp.int32(-1), first)


# Max columns of one allowMultiple resolution group (same (type, attr-id) —
# in practice the add and removes of one comment id) the cached patch scan
# resolves exactly.  The universe checks group sizes host-side and falls
# back to the interleaved scan when exceeded, so the cap never silently
# changes results.  Read at import time.
PATCH_GROUP_K = int(os.environ.get("PERITEXT_PATCH_GROUP_K", "32"))


def _winner_cache_init(bnd_mask0, mark_cols, ranks, n_types, max_mark_ops, multi):
    """Per-slot per-type LWW winners of the pre-batch boundary rows.

    The patch scan's ``changed`` signal needs, per mark op, the winner of
    the op's own resolution group within the inherited set at each written
    slot (opsToMarks restricted to one group, peritext.ts:294-326).  For
    non-allowMultiple types the group is the TYPE, so the winner is a
    per-slot per-type quantity — cacheable as [2C, T, 4] (ctr, rank,
    action, attr; ctr=-1 empty) and maintainable through the scan with the
    same carry gathers _apply_mark already does.

    Resolution is ONE dominance matmul for all types at once (the
    resolve_winners trick — a [2C, M] x [M, M] MXU pass), not a per-type
    loop of [2C, M] reductions; with the padded type registry (T=16) the
    loop form materializes ~100 [2C, M] planes per replica and dominates
    the whole merge.  Winner VALUES are recovered via an index matmul
    (win @ one-hot·(index+1), exact in f32 since indices < 2^24) followed
    by [2C, T] gathers — never by summing raw field values through f32.
    Entries for allowMultiple types are unused (their groups are per-attr;
    the scan resolves them over compacted columns).
    """
    mark_ctr, mark_act, mark_action, mark_type, mark_attr = mark_cols
    m_cap = mark_ctr.shape[0]
    present = expand_mask_bits(bnd_mask0, max_mark_ops)  # [2C, M] bool
    rank = ranks[mark_act]
    type_c = jnp.clip(mark_type, 0, n_types - 1)
    nm_col = ~multi[type_c]  # non-allowMultiple columns

    same_type = mark_type[:, None] == mark_type[None, :]
    key_gt = (mark_ctr[None, :] > mark_ctr[:, None]) | (
        (mark_ctr[None, :] == mark_ctr[:, None]) & (rank[None, :] > rank[:, None])
    )
    dom = same_type & key_gt & nm_col[:, None] & nm_col[None, :]
    # dom_count[p, n] = #present dominators of column n at slot p.
    dom_count = jnp.einsum(
        "pm,nm->pn", present.astype(jnp.float32), dom.astype(jnp.float32)
    )
    win = present & nm_col[None, :] & (dom_count < 0.5)  # one-hot per (slot, type)

    onehot = (
        (type_c[:, None] == jnp.arange(n_types, dtype=jnp.int32)[None, :])
        & nm_col[:, None]
    ).astype(jnp.float32)  # [M, T]
    col_plus1 = (jnp.arange(m_cap, dtype=jnp.int32) + 1).astype(jnp.float32)
    # Precision.HIGHEST: TPU default matmul precision feeds bf16 operands
    # to the MXU, and column indices above 256 are not bf16-representable —
    # the recovered winner column would silently drift.  (The dominance
    # einsum above is safe at any precision: 0/1 operands, f32 accumulate.)
    widx = (
        jnp.round(
            jnp.matmul(
                win.astype(jnp.float32),
                onehot * col_plus1[:, None],
                precision=lax.Precision.HIGHEST,
            )
        ).astype(jnp.int32)
        - 1
    )  # [2C, T]: winner column, -1 when none
    has = widx >= 0
    wc = jnp.maximum(widx, 0)
    return jnp.where(
        has[:, :, None],
        jnp.stack(
            [mark_ctr[wc], rank[wc], mark_action[wc], mark_attr[wc]], axis=-1
        ),
        jnp.array([-1, -1, 0, 0], jnp.int32)[None, None, :],
    )  # [2C, T, 4]


def _permute_wcache(wcache, orig_idx):
    """Re-align a [2C, T, 4] winner cache after a text phase, mirroring
    _permute_boundaries: batch-born elements' slots come up empty.

    Flat single-axis slot gather (no [C, 2, T, 4] view): the view-reshaped
    gather cost the compiler SIX full-plane layout copies of the [2C, T, 4]
    cache — 1.5 GiB/launch at the bench shape, the threaded patched path's
    single largest traffic source (PROFILE_r05.md)."""
    valid, flat_src = _slot_permutation(orig_idx)
    return jnp.where(
        valid[:, None, None],
        wcache[flat_src],
        jnp.array([-1, -1, 0, 0], jnp.int32)[None, None, :],
    )


def _group_topk_cols(mark_type_col, mark_attr_col, op, k: int):
    """Indices of up to ``k`` mark-table columns in op's (type, attr) group
    (exhaustive when the host-verified group size is <= k), plus validity."""
    match = (mark_type_col == op[K_MTYPE]) & (mark_attr_col == op[K_MATTR])
    # A group can never exceed the table itself; clamping keeps an oversized
    # PERITEXT_PATCH_GROUP_K from making top_k request more lanes than exist.
    vals, cols = lax.top_k(match.astype(jnp.int32), min(k, match.shape[0]))
    return cols.astype(jnp.int32), vals > 0


def _winner_over_cand(cand, g_ctr, g_rank, g_action, g_attr):
    """LWW winner per row among candidate columns (``cand`` [N, K] bool with
    per-column key/value vectors [K]).  The shared reduction core of
    _winner_over_cols and the compact-delta scan's group resolution — one
    definition, so the two patched paths cannot diverge on tie-breaks."""
    neg = jnp.int32(-(2**31) + 1)
    ctrs = jnp.where(cand, g_ctr[None, :], neg)
    max_ctr = jnp.max(ctrs, axis=1)
    tie = cand & (g_ctr[None, :] == max_ctr[:, None])
    rks = jnp.where(tie, g_rank[None, :], neg)
    max_rank = jnp.max(rks, axis=1)
    win = tie & (g_rank[None, :] == max_rank[:, None])
    has = cand.any(axis=1)
    w_action = jnp.sum(jnp.where(win, g_action[None, :], 0), axis=1)
    w_attr = jnp.sum(jnp.where(win, g_attr[None, :], 0), axis=1)
    return (
        jnp.where(has, max_ctr, jnp.int32(-1)),
        jnp.where(has, max_rank, jnp.int32(-1)),
        w_action,
        w_attr,
        has,
    )


def _winner_over_cols(carry, cols, col_ok, mark_cols, ranks):
    """LWW winner per slot among the given table columns present in the
    carry rows: [2C, K] work instead of [2C, M]."""
    mark_ctr, mark_act, mark_action, _mark_type, mark_attr = mark_cols
    words = (cols // MASK_WORD_BITS).astype(jnp.int32)
    bits = (cols % MASK_WORD_BITS).astype(jnp.uint32)
    pres = (jnp.take(carry, words, axis=1) >> bits[None, :]) & jnp.uint32(1)
    cand = pres.astype(bool) & col_ok[None, :]  # [2C, K]
    return _winner_over_cand(
        cand, mark_ctr[cols], ranks[mark_act[cols]], mark_action[cols], mark_attr[cols]
    )


def _delta_mark_scan(
    bnd_mask_base,
    wcache0,
    mark_ops,
    mark_time,
    mcols_final,
    elem_ctr,
    elem_act,
    length,
    born,
    died,
    def_first,
    src_ok,
    src_c,
    tm,
    mark_count0,
    ranks,
    multi,
    group_k: int,
    has_multi: bool,
    t_act: int,
    perm=None,
):
    """Compact-delta mark-row scan (the default patched path).

    Emits per-step patch records identical to the dense scan in
    merge_step_sorted_patched — the differential bar is byte-identical
    assembled Patch streams AND byte-identical post-merge planes — but the
    full boundary plane is read once and written once per launch, and the
    winner cache moves through the scan with slot-local writes instead of
    full-plane selects:

    - ``root_src`` [2C] i32: which slot's PRE-batch row is the full-width
      base of each slot's current row (-1: zero row).  An anchor write
      (the rebase write class on _apply_mark) copies its carry source's
      *pointer* instead of its [W]-word row; pre-batch bits are recovered
      by composed index reads into the untouched ``bnd_mask0`` plane.
    - ``win_bits`` [2C, w_act] u32: the active word WINDOW of every row
      (the only words the batch's new bits can land in — the same window
      rule as _apply_marks_batch).  In-range bit ORs are one-word-column
      read-modify-writes; anchor writes copy one row.
    - ``bw`` [T, 2C] i32: the winning BATCH table column per (type, slot)
      among this batch's non-allowMultiple ops so far (-1: none).  The
      dense scan's carried ``[2C, T, 4]`` cache value at any slot is
      exactly ``LWW(wcache0[root_src[slot]], entry(bw[:, slot]))`` — max
      over (ctr, rank) is associative, so recording every in-range batch
      op in ``bw`` and composing against the untouched base cache gives
      byte-identical winners without gating on the composed current value.
      Anchor writes copy one ``[T]`` column (plus the root pointer); the
      full ``[2C, T, 4]`` cache plane is read once (composed gathers) and
      written once by the post-scan compose, never carried.
    - ``acc_root``/``acc_win``: the insert rows' inherited-row composition
      captured at their instants (composed to full [Lt, W] rows after the
      scan).

    The gated writes use the write-unconditionally/select-the-VALUE shape
    (``col: where(gate, new, cur); plane: dus(plane, col)``) so XLA keeps
    the carried buffers in place — ``where(gate, dus(..), plane)`` costs a
    full-plane copy per step.  allowMultiple group resolution only
    compiles when the batch actually carries multi ops (``has_multi``),
    at the host-census-measured width ``group_k`` ≤ PATCH_GROUP_K.
    ``t_act`` (static, the registry-size pow2 bucket ≤ MAX_MARK_TYPES)
    sizes the carried batch-winner table's type axis: valid ops' type ids
    are < NUM_MARK_TYPES ≤ t_act, so the dead padding types — the cache
    plane is padded to MAX_MARK_TYPES so registration never recompiles —
    drop out of the per-step traversal; base-plane types ≥ t_act pass
    through the final compose untouched.
    """
    mark_ctr_f, mark_act_f, mark_action_f, mark_type_f, mark_attr_f = mcols_final
    c = elem_ctr.shape[0]
    two_c = 2 * c
    m_ops = mark_ops.shape[0]
    w_words = bnd_mask_base.shape[-1]
    n_types = multi.shape[0]
    slots = jnp.arange(two_c, dtype=jnp.int32)
    ar_c = jnp.arange(c, dtype=jnp.int32)
    live_c = ar_c < length
    empty_wc = jnp.array([-1, -1, 0, 0], jnp.int32)
    type_ar = jnp.arange(t_act, dtype=jnp.int32)

    valid, s_slots, e_slots = _batched_anchor_slots(
        mark_ops, elem_ctr, elem_act, length
    )
    m_idx0 = jnp.arange(m_ops, dtype=jnp.int32)

    # Window geometry (same rule as _apply_marks_batch; valid rows are a
    # prefix, so op m's table column is mark_count0 + m).
    w_act = min((m_ops + MASK_WORD_BITS - 1) // MASK_WORD_BITS + 1, w_words)
    w0 = jnp.clip(mark_count0 // MASK_WORD_BITS, 0, w_words - w_act)
    word_ar = jnp.arange(w_act, dtype=jnp.int32)
    bit_off = mark_count0 + m_idx0 - w0 * MASK_WORD_BITS  # [M] window-relative
    op_rank_v = ranks[mark_ops[:, K_ACT]]
    tau_v = jnp.clip(mark_ops[:, K_MTYPE], 0, t_act - 1)
    is_multi_v = multi[tau_v]

    # The text phase's boundary permutation composes INTO every base-plane
    # read: with ``perm`` given, ``bnd_mask_base`` AND ``wcache0`` are the
    # RAW pre-splice planes and
    # no permuted [2C, W] / [2C, T, 4] copy is ever materialized — both
    # planes are only read through composed gathers and written once by
    # the final composes.
    if perm is not None:
        pvalid, pflat = perm

        def base_rows(idx, ok):  # post-splice slots -> full-width base rows
            okc = ok & pvalid[idx]
            return jnp.where(
                okc[:, None],
                bnd_mask_base[pflat[idx]],
                jnp.uint32(0),
            )

        def base_words(idx, ok, words):  # [N] slots x [K] words -> [N, K]
            okc = ok & pvalid[idx]
            return jnp.where(
                okc[:, None],
                bnd_mask_base[pflat[idx][:, None], words[None, :]],
                jnp.uint32(0),
            )

        def base_wc_rows(idx, ok):  # slots -> [N, T, 4] base cache rows
            okc = ok & pvalid[idx]
            return jnp.where(
                okc[:, None, None],
                wcache0[pflat[idx]],
                empty_wc[None, None, :],
            )

        def base_wc_tau(idx, ok, t):  # slots -> [N, 4] entries at type t
            okc = ok & pvalid[idx]
            return jnp.where(
                okc[:, None], wcache0[pflat[idx], t], empty_wc[None, :]
            )

    else:

        def base_rows(idx, ok):
            return jnp.where(ok[:, None], bnd_mask_base[idx], jnp.uint32(0))

        def base_words(idx, ok, words):
            return jnp.where(
                ok[:, None],
                bnd_mask_base[idx[:, None], words[None, :]],
                jnp.uint32(0),
            )

        def base_wc_rows(idx, ok):
            return jnp.where(
                ok[:, None, None], wcache0[idx], empty_wc[None, None, :]
            )

        def base_wc_tau(idx, ok, t):
            return jnp.where(ok[:, None], wcache0[idx, t], empty_wc[None, :])

    # Carry-independent signals, hoisted OUT of the scan and computed in
    # one batched pass over the op axis (identical per-op semantics: the
    # same _walk_signals definition, vmapped).  The scan body keeps only
    # the carry-dependent work (`changed` + the plane updates).
    defined_all = def_first[None, :] < m_idx0[:, None]  # [M, 2C]
    visible_all = (
        live_c[None, :]
        & (born[None, :] < mark_time[:, None])
        & (died[None, :] > mark_time[:, None])
    )  # [M, C]
    written_all, during_all, vis_all, final_vis_all = jax.vmap(
        lambda s, e, d, v: _walk_signals((s, e, slots, d, None, None), v, c)
    )(s_slots, e_slots, defined_all, visible_all)
    src_q_all = lax.cummax(
        jnp.where(defined_all, slots[None, :], jnp.int32(-1)), axis=1
    )  # [M, 2C]

    def compose_rows(root, win_rows):
        """(root pointer [N], window words [N, w_act]) -> full [N, W] rows:
        one gather into the untouched base plane + the w_act static
        broadcast-selects of the window write-back (PROFILE_r05 step 3)."""
        base = base_rows(jnp.maximum(root, 0), root >= 0)
        word_full = jnp.arange(w_words, dtype=jnp.int32)
        out = base
        for j in range(w_act):
            out = jnp.where(
                word_full[None, :] == w0 + j, win_rows[:, j][:, None], out
            )
        return out

    carry0 = (
        slots,  # root_src: every slot starts as its own base row
        base_words(slots, slots >= 0, w0 + word_ar),
        jnp.full((t_act, two_c), -1, jnp.int32),  # bw: no batch winner yet
        jnp.full(src_c.shape[0], -1, jnp.int32),  # acc_root
        jnp.zeros((src_c.shape[0], w_act), jnp.uint32),  # acc_win
    )
    xs = (
        mark_ops,
        m_idx0,
        s_slots,
        e_slots,
        valid,
        bit_off,
        op_rank_v,
        tau_v,
        is_multi_v,
        during_all,
        src_q_all,
    )

    def bw_entry(colv):
        """Batch-winner table columns -> (ctr, rank, action, attr) entries
        ([-1, -1, 0, 0] where no batch winner); columns >= mark_count0 are
        exactly this batch's ops, so the final mark table holds them."""
        ok = colv >= 0
        cc = jnp.maximum(colv, 0)
        return jnp.stack(
            [
                jnp.where(ok, mark_ctr_f[cc], jnp.int32(-1)),
                jnp.where(ok, ranks[mark_act_f[cc]], jnp.int32(-1)),
                jnp.where(ok, mark_action_f[cc], jnp.int32(0)),
                jnp.where(ok, mark_attr_f[cc], jnp.int32(0)),
            ],
            axis=-1,
        )

    def lww(a, b):
        """Pick b where it beats a on (ctr, rank) — the dense scan's
        `beats` rule, applied entrywise to [..., 4] cache entries."""
        pick = (b[..., 0] > a[..., 0]) | (
            (b[..., 0] == a[..., 0]) & (b[..., 1] > a[..., 1])
        )
        return jnp.where(pick[..., None], b, a)

    def step(carry, xs_t):
        root_src, win_bits, bw, acc_root, acc_win = carry
        (op, m_idx, s_sl, e_sl, val, bo, op_rank, tau, is_mop,
         during, src_q) = xs_t
        wb = bo // MASK_WORD_BITS
        bit_u = jnp.uint32(1) << (bo % MASK_WORD_BITS).astype(jnp.uint32)
        defined = def_first < m_idx

        # Inserts whose instant lands at this plane version capture their
        # inherited row's composition BEFORE this mark writes (same
        # read-at-step-start as the dense scan; pad steps never write, so
        # any tm landing on a pad index still reads the right version).
        take = src_ok & (tm == m_idx)
        acc_root = jnp.where(take, root_src[src_c], acc_root)
        acc_win = jnp.where(take[:, None], win_bits[src_c], acc_win)

        # `changed`: the op's group winner within the inherited set at each
        # slot's carry source, composed on the fly — the untouched base
        # cache gathered at the source's ROOT, LWW'd against the carried
        # batch-winner column — a few [2C] gathers where the dense scan
        # materialized a full [2C, T, 4] carry select.
        q_ok = src_q >= 0
        qc = jnp.maximum(src_q, 0)
        rootq = jnp.where(q_ok, root_src[qc], jnp.int32(-1))
        rq_ok = rootq >= 0
        rqc = jnp.maximum(rootq, 0)
        bw_tau = lax.dynamic_slice(bw, (tau, 0), (1, two_c))[0]  # [2C]
        base_e = base_wc_tau(rqc, rq_ok, tau)
        wnm = lww(
            base_e, bw_entry(jnp.where(q_ok, bw_tau[qc], jnp.int32(-1)))
        )  # [2C, 4]
        w_ctr, w_rank = wnm[:, 0], wnm[:, 1]
        w_action, w_attr = wnm[:, 2], wnm[:, 3]
        has_winner = w_ctr >= 0

        if has_multi:
            # allowMultiple groups resolve over their (host-gated, host-
            # sized) compacted columns; presence composes window words from
            # the carry with non-window words from the untouched base plane
            # at the row's root (rootq/rqc shared with the `changed` read).
            cols, col_ok = _group_topk_cols(mark_type_f, mark_attr_f, op, group_k)
            words = (cols // MASK_WORD_BITS).astype(jnp.int32)
            bits = (cols % MASK_WORD_BITS).astype(jnp.uint32)
            in_win = (words >= w0) & (words < w0 + w_act)
            win_part = jnp.take(
                win_bits[qc], jnp.clip(words - w0, 0, w_act - 1), axis=1
            )
            base_part = base_words(rqc, rq_ok, words)
            word_val = jnp.where(
                q_ok[:, None],
                jnp.where(in_win[None, :], win_part, base_part),
                jnp.uint32(0),
            )
            pres = ((word_val >> bits[None, :]) & jnp.uint32(1)).astype(bool)
            g_ctr, g_rank, g_action, g_attr, g_has = _winner_over_cand(
                pres & col_ok[None, :],
                mark_ctr_f[cols],
                ranks[mark_act_f[cols]],
                mark_action_f[cols],
                mark_attr_f[cols],
            )
            w_ctr = jnp.where(is_mop, g_ctr, w_ctr)
            w_rank = jnp.where(is_mop, g_rank, w_rank)
            w_action = jnp.where(is_mop, g_action, w_action)
            w_attr = jnp.where(is_mop, g_attr, w_attr)
            has_winner = jnp.where(is_mop, g_has, has_winner)

        changed = _changed_vs_winner(
            op, op_rank, w_ctr, w_rank, w_action, w_attr, has_winner
        )

        # --- apply the op to the carry ---------------------------------
        # All write values read the PRE-update carry (the dense scan's
        # writes are simultaneous); every update is an ELEMENTWISE select
        # keyed on slot one-hots, so XLA fuses the whole chain into one
        # traversal of each carried plane per step.  (The batched-index
        # dynamic-update-slice formulation lowers to near-serial scatters
        # under vmap on CPU and to per-replica sub-loops on TPU — measured
        # strictly worse on both.)
        s_lt_e = s_sl < e_sl
        write_s = val & s_lt_e
        write_e = val & (e_sl < two_c)
        e_cl = jnp.minimum(e_sl, jnp.int32(two_c - 1))
        q_s = src_q[s_sl]
        q_e = src_q[e_cl]
        root_s_v = jnp.where(q_s >= 0, root_src[jnp.maximum(q_s, 0)], jnp.int32(-1))
        root_e_v = jnp.where(q_e >= 0, root_src[jnp.maximum(q_e, 0)], jnp.int32(-1))
        win_row_s = jnp.where(q_s >= 0, win_bits[jnp.maximum(q_s, 0)], jnp.uint32(0))
        win_row_e = jnp.where(q_e >= 0, win_bits[jnp.maximum(q_e, 0)], jnp.uint32(0))
        col_s = jnp.where(q_s >= 0, bw[:, jnp.maximum(q_s, 0)], jnp.int32(-1))
        col_e = jnp.where(q_e >= 0, bw[:, jnp.maximum(q_e, 0)], jnp.int32(-1))
        one_s = (slots == s_sl) & write_s
        one_e = (slots == e_cl) & write_e
        inr_def = during & defined & val

        # Window words: in-range bit OR (only the op's word can change) +
        # the two anchor-row rebases.
        bit_at = inr_def[:, None] & (word_ar == wb)[None, :]
        win_bits = jnp.where(bit_at, win_bits | bit_u, win_bits)
        bit_row = jnp.where(word_ar == wb, bit_u, jnp.uint32(0))
        win_bits = jnp.where(one_s[:, None], (win_row_s | bit_row)[None, :], win_bits)
        win_bits = jnp.where(one_e[:, None], win_row_e[None, :], win_bits)
        root_src = jnp.where(one_s, root_s_v, root_src)
        root_src = jnp.where(one_e, root_e_v, root_src)

        # Batch-winner table: record the op's column into its own type's
        # row over in-range defined slots where it beats the current BATCH
        # winner (non-allowMultiple only — the dense `beats_nm` update
        # class; gating on the composed-with-base value is unnecessary,
        # max over (ctr, rank) is associative and the final compose takes
        # the same max), then the two anchor-COLUMN rebases.
        cur = bw_entry(bw_tau)
        beats = (bw_tau < 0) | (op[K_CTR] > cur[:, 0]) | (
            (op[K_CTR] == cur[:, 0]) & (op_rank > cur[:, 1])
        )
        tau_oh = type_ar == tau
        upd_inr = inr_def & ~is_mop & beats
        op_col = mark_count0 + m_idx
        bw = jnp.where(upd_inr[None, :] & tau_oh[:, None], op_col, bw)
        cs_tau = col_s[tau]
        cs = bw_entry(cs_tau[None])[0]
        s_beats = (cs_tau < 0) | (op[K_CTR] > cs[0]) | (
            (op[K_CTR] == cs[0]) & (op_rank > cs[1])
        )
        new_col = jnp.where(~is_mop & s_beats, op_col, cs_tau)
        col_s = jnp.where(tau_oh, new_col, col_s)
        bw = jnp.where(one_s[None, :], col_s[:, None], bw)
        bw = jnp.where(one_e[None, :], col_e[:, None], bw)

        return (root_src, win_bits, bw, acc_root, acc_win), changed & val

    (root_src_f, win_f, bw_f, acc_root, acc_win), changed_all = lax.scan(
        step, carry0, xs
    )
    mrec = {
        "written": written_all & valid[:, None],
        "during": during_all & valid[:, None],
        "changed": changed_all,
        "vis": vis_all,
        "obj_len": final_vis_all,
    }

    # Inserts after every mark instant read the final composition.
    take_f = src_ok & (tm == m_ops)
    acc_root = jnp.where(take_f, root_src_f[src_c], acc_root)
    acc_win = jnp.where(take_f[:, None], win_f[src_c], acc_win)
    ins_mask = compose_rows(acc_root, acc_win)

    # Final planes: ONE composed gather over the untouched base plane +
    # the window write-back; definedness is fully analytic (anchor writes
    # are the only first definitions, _sorted_def_first); the final winner
    # cache composes the same way — base rows gathered at each slot's
    # root, LWW'd against the batch winners — the launch's only full
    # [2C, T, 4] read + write.
    new_mask = compose_rows(root_src_f, win_f)
    new_def = def_first <= m_ops
    base_wc = base_wc_rows(jnp.maximum(root_src_f, 0), root_src_f >= 0)
    bw_vals = jnp.swapaxes(bw_entry(bw_f), 0, 1)  # [2C, t_act, 4]
    wcache_f = jnp.concatenate(
        [lww(base_wc[:, :t_act], bw_vals), base_wc[:, t_act:]], axis=1
    )
    return new_def, new_mask, ins_mask, mrec, wcache_f


def merge_step_sorted_patched(
    state: DocState,
    text_ops: jax.Array,
    round_of: jax.Array,
    num_rounds: jax.Array,
    mark_ops: jax.Array,
    ranks: jax.Array,
    char_buf: jax.Array,
    multi: jax.Array,
    text_time: jax.Array,
    mark_time: jax.Array,
    maxk: int,
    has_marks: bool = True,
    wcache_in: jax.Array | None = None,
    mode: str = "delta",
    group_k: int | None = None,
    has_multi: bool = True,
    t_act: int | None = None,
    readback: str = "planes",
    span_cap: int = 8,
    cand_cap: int = 64,
    vis_base: jax.Array | None = None,
    vis_after: jax.Array | None = None,
):
    """Sorted merge that also emits per-op patch records.

    Produces the exact interleaved-path records (apply_ops_patched) for the
    same delivery order — differential bar: byte-identical assembled Patch
    streams (tests/test_engine_patches, tests/test_sorted_merge) — while
    the text phase runs in O(depth) placement rounds and the scan covers
    only the batch's mark rows.  ``text_time`` / ``mark_time`` are each
    row's flat delivery-stream position (encode row_pos; a fused run's
    first char), padded with a beyond-any-instant sentinel.

    ``wcache_in`` (optional [2C, T, 4], PRE-placement slot coordinates):
    the persisted per-slot per-type winner cache from the previous patched
    merge — the universe threads it between ingests so the [2C, M]
    dominance init amortizes to ONE launch per universe lifetime in an
    all-patched (editor-fleet) workload.  It is derived state: exactly the
    cache a fresh init over the same boundary rows would produce
    (tests assert this), permuted alongside the boundary planes here.
    Returns ``(new_state, records)``; records carry ``wcache`` (final,
    post-batch coordinates) for the universe to persist — except on the
    cacheless mark-free path, which neither needs nor produces one.

    ``mode`` selects the mark-row scan's carry representation: "delta"
    (default) runs the compact-delta scan (_delta_mark_scan — composition
    pointers + window words carried; the full [2C, W] / [2C, T, 4] planes
    read once and written once per launch), "dense" the original
    full-plane-carry scan below.  Both are byte-identical in records and
    state; PERITEXT_PATCH_PATH=dense forces the dense variant for A/B.
    ``group_k``/``has_multi`` statically specialize the delta scan's
    allowMultiple group resolution from the host census.

    ``readback`` selects the record *transfer* format (orthogonal to the
    scan-carry ``mode``): "planes" returns the full per-slot mark planes
    (today's path, the A/B baseline), "compact" reduces them on device to
    ``[M, span_cap]`` run tables via :func:`compact_mark_records` and
    drops host-redundant fields (``kind`` — the encoded text rows already
    carry it), so the D2H readback is proportional to the emitted patches
    instead of the document.  ``mcount`` carries the true span count; the
    universe falls back to a planes launch when any row overflows
    ``span_cap``, so both formats always assemble byte-identical streams.
    ``cand_cap`` statically sizes the compaction's defined-slot candidate
    axis from the host's mark-count mirror (defined slots never exceed 2x
    the mark table — see compact_mark_records).

    ``vis_base``/``vis_after`` (traced scalars; None = whole-table merge)
    re-anchor the record coordinates when the merge runs over a gathered
    WINDOW of the document (the frontier-bounded path): every visibleIndex
    the records carry is window-local, and the true index adds the count
    of visible elements before the window (``vis_base``); the instant's
    objLength adds the visible elements on both sides (text edits all land
    inside the window, so both counts are batch-invariant).  The offsets
    apply BEFORE span compaction so the finishPartialPatch filters and end
    clamps run on global coordinates — byte-identical records to the
    full-table merge on either readback format.
    """

    def _finish_records(records, cand_def):
        if vis_base is not None:
            records = dict(records)
            records["index0"] = records["index0"] + vis_base
            records["vis"] = records["vis"] + vis_base
            records["obj_len"] = records["obj_len"] + vis_base + vis_after
        if readback != "compact":
            return records
        if cand_def is None:
            # Mark-free fast path: no mark rows, hence no spans — the run
            # tables are statically empty.
            m_pad = records["written"].shape[0]
            run_start = jnp.zeros((m_pad, span_cap), jnp.int32)
            run_end = jnp.zeros((m_pad, span_cap), jnp.int32)
            count = jnp.zeros((m_pad,), jnp.int32)
        else:
            run_start, run_end, count = compact_mark_records(
                records["written"], records["during"], records["changed"],
                records["vis"], records["obj_len"], cand_def, span_cap, cand_cap,
            )
        out = {
            "tvalid": records["tvalid"],
            "index0": records["index0"],
            "ins_mask": records["ins_mask"],
            "mstart": run_start,
            "mend": run_end,
            "mcount": count,
        }
        if "wcache" in records:
            out["wcache"] = records["wcache"]
        return out

    elem_ctr, elem_act, deleted, chars, orig_idx, length = place_text_batch(
        state.elem_ctr,
        state.elem_act,
        state.deleted,
        state.chars,
        state.length,
        text_ops,
        round_of,
        num_rounds,
        ranks,
        char_buf,
        maxk,
    )
    pvalid_p, pflat_p = _slot_permutation(orig_idx)
    bnd_def0 = jnp.where(pvalid_p, state.bnd_def[pflat_p], False)
    # The compact-delta warm path never materializes the permuted mask
    # plane: the scan reads the RAW plane through the composed permutation
    # and writes the final plane once (its compose).  Every other path
    # (dense, mark-free, and the cold dominance init, which expands the
    # full plane anyway) materializes it here, exactly as before.
    delta_composed = mode == "delta" and has_marks and wcache_in is not None
    bnd_mask0 = (
        None
        if delta_composed
        else jnp.where(pvalid_p[:, None], state.bnd_mask[pflat_p], jnp.uint32(0))
    )
    mark_valid = mark_ops[:, K_KIND] == KIND_MARK
    born, died, q, index0, tvalid, tm = _sorted_text_records(
        elem_ctr, elem_act, orig_idx, length, state.deleted,
        text_ops, text_time, mark_time, mark_valid,
    )

    # Inherited-marks source per insert row (getActiveMarksAtIndex,
    # peritext.ts:328-330): nearest slot left of the insertion gap that is
    # defined at the row's instant.  All chars of a delivery-adjacent run
    # share it (the run's own fresh slots are undefined).
    c = elem_ctr.shape[0]
    slots = jnp.arange(2 * c, dtype=jnp.int32)
    def_first = _sorted_def_first(bnd_def0, mark_ops, elem_ctr, elem_act, length)
    kind_t = text_ops[:, K_KIND]
    is_ins = (kind_t == KIND_INSERT) | (kind_t == KIND_INSERT_RUN)
    src = jnp.max(
        jnp.where(
            (def_first[None, :] < tm[:, None]) & (slots[None, :] < 2 * q[:, None]),
            slots[None, :],
            jnp.int32(-1),
        ),
        axis=1,
    )
    src_ok = (src >= 0) & is_ins
    src_c = jnp.maximum(src, 0)

    # Mark table appended up front: the scan resolves winners against final
    # columns, with per-step mark_count restricting candidates to ops
    # already applied (present bits can't contain later ops anyway).
    mark_cols = _append_mark_table(
        (state.mark_ctr, state.mark_act, state.mark_action, state.mark_type, state.mark_attr),
        mark_ops,
        state.mark_count,
        state.max_mark_ops,
    )
    mark_ctr_f, mark_act_f, mark_action_f, mark_type_f, mark_attr_f, mark_count_f = mark_cols

    w = state.bnd_mask.shape[-1]
    acc0 = jnp.zeros((text_ops.shape[0], w), jnp.uint32)
    m_idx0 = jnp.arange(mark_ops.shape[0], dtype=jnp.int32)

    # Per-slot per-type winner cache: the scan's `changed` signal resolves
    # the op's group winner from this [2C, T, 4] cache (non-allowMultiple)
    # or a K-compacted column subset (allowMultiple; host-gated to the
    # interleaved fallback when a group exceeds PATCH_GROUP_K) instead of
    # expanding a [2C, M] presence plane per step — the patched path's
    # dominant traffic (PROFILE_r04.md item 3).
    mcols_final = (mark_ctr_f, mark_act_f, mark_action_f, mark_type_f, mark_attr_f)
    n_types = multi.shape[0]

    if not has_marks:
        # Static no-marks fast path (the common pure-typing batch, chosen
        # by the universe from the encoded rows): boundary planes never
        # evolve, so inserts inherit straight from the pre-scan planes and
        # the winner-cache init + mark scan compile away entirely.
        rows0 = bnd_mask0[src_c]
        ins_mask = jnp.where((src_ok)[:, None], rows0, jnp.uint32(0))
        m_pad = mark_ops.shape[0]
        new_state = DocState(
            elem_ctr=elem_ctr,
            elem_act=elem_act,
            deleted=deleted,
            chars=chars,
            bnd_def=bnd_def0,
            bnd_mask=bnd_mask0,
            mark_ctr=mark_ctr_f,
            mark_act=mark_act_f,
            mark_action=mark_action_f,
            mark_type=mark_type_f,
            mark_attr=mark_attr_f,
            length=length,
            mark_count=mark_count_f,
        )
        records = {
            "kind": kind_t,
            "tvalid": tvalid,
            "index0": index0,
            "ins_mask": ins_mask,
            "written": jnp.zeros((m_pad, 2 * c), bool),
            "during": jnp.zeros((m_pad, 2 * c), bool),
            "changed": jnp.zeros((m_pad, 2 * c), bool),
            "vis": jnp.zeros((m_pad, 2 * c), jnp.int32),
            "obj_len": jnp.zeros((m_pad,), jnp.int32),
        }
        if wcache_in is not None:
            # Rows didn't evolve; the persisted cache stays valid once
            # realigned to the new slot coordinates.
            records["wcache"] = _permute_wcache(wcache_in, orig_idx)
        return new_state, _finish_records(records, None)

    # The compact-delta warm path also never materializes the permuted
    # winner cache: the scan reads the cache only through gathers, so the
    # slot permutation composes into them exactly as the boundary plane's
    # does, and the [2C, T, 4] permute copy disappears from the launch.
    if delta_composed:
        wcache0 = wcache_in
    else:
        wcache0 = (
            _permute_wcache(wcache_in, orig_idx)
            if wcache_in is not None
            else _winner_cache_init(
                bnd_mask0, mcols_final, ranks, n_types, state.max_mark_ops, multi
            )
        )

    if mode == "delta":
        # Compact-delta mark-row scan: the carry holds only the batch's
        # composition state; the full [2C, W] / [2C, T, 4] planes are read
        # once and written once per launch (see _delta_mark_scan).
        bnd_def, bnd_mask, ins_mask, mrec, wcache_f = _delta_mark_scan(
            state.bnd_mask if delta_composed else bnd_mask0,
            wcache0,
            mark_ops,
            mark_time,
            mcols_final,
            elem_ctr,
            elem_act,
            length,
            born,
            died,
            def_first,
            src_ok,
            src_c,
            tm,
            state.mark_count,
            ranks,
            multi,
            group_k if group_k is not None else PATCH_GROUP_K,
            has_multi,
            t_act if t_act is not None else n_types,
            perm=(pvalid_p, pflat_p) if delta_composed else None,
        )
        new_state = DocState(
            elem_ctr=elem_ctr,
            elem_act=elem_act,
            deleted=deleted,
            chars=chars,
            bnd_def=bnd_def,
            bnd_mask=bnd_mask,
            mark_ctr=mark_ctr_f,
            mark_act=mark_act_f,
            mark_action=mark_action_f,
            mark_type=mark_type_f,
            mark_attr=mark_attr_f,
            length=length,
            mark_count=mark_count_f,
        )
        records = {
            "kind": kind_t,
            "tvalid": tvalid,
            "index0": index0,
            "ins_mask": ins_mask,
            "written": mrec["written"],
            "during": mrec["during"],
            "changed": mrec["changed"],
            "vis": mrec["vis"],
            "obj_len": mrec["obj_len"],
            "wcache": wcache_f,
        }
        return new_state, _finish_records(records, bnd_def)

    ar_c = jnp.arange(c, dtype=jnp.int32)
    empty_wc = jnp.array([-1, -1, 0, 0], jnp.int32)

    def step(carry, xs):
        bnd_def, bnd_mask, acc, wcache = carry
        op, m_idx, t_m = xs
        # Inserts whose instant lands at this plane version read their
        # inherited row before this mark writes.  (Valid mark rows are a
        # prefix; pad steps leave the planes untouched, so any tm landing
        # on a pad index still reads the right version.)
        rows = bnd_mask[src_c]  # [Lt, W]
        take = src_ok & (tm == m_idx)
        acc = acc | jnp.where(take[:, None], rows, jnp.uint32(0))

        # Synthetic state view: final text plane with visibility masked to
        # this instant, evolving boundary planes, final mark table.
        st_deleted = ~((born < t_m) & (died > t_m))
        st = DocState(
            elem_ctr=elem_ctr,
            elem_act=elem_act,
            deleted=st_deleted,
            chars=chars,
            bnd_def=bnd_def,
            bnd_mask=bnd_mask,
            mark_ctr=mark_ctr_f,
            mark_act=mark_act_f,
            mark_action=mark_action_f,
            mark_type=mark_type_f,
            mark_attr=mark_attr_f,
            length=length,
            mark_count=state.mark_count + m_idx,
        )
        valid = op[K_KIND] == KIND_MARK
        ctx = _mark_slot_context(st, op)
        carry_rows, src = ctx[4], ctx[5]
        written, during, vis, final_vis = _walk_signals(
            ctx, (ar_c < length) & ~st_deleted, c
        )

        # `changed`: winner of the op's resolution group within the
        # inherited set, from the cache (LWW-per-type) or the compacted
        # group columns (allowMultiple).
        src_ok_slot = src >= 0
        srcc = jnp.maximum(src, 0)
        wc_carry = jnp.where(
            src_ok_slot[:, None, None], wcache[srcc], empty_wc[None, None, :]
        )  # [2C, T, 4]
        wnm = jnp.take(wc_carry, jnp.clip(op[K_MTYPE], 0, n_types - 1), axis=1)
        cols, col_ok = _group_topk_cols(mark_type_f, mark_attr_f, op, PATCH_GROUP_K)
        g_ctr, g_rank, g_action, g_attr, g_has = _winner_over_cols(
            carry_rows, cols, col_ok, mcols_final, ranks
        )
        is_multi_op = multi[jnp.clip(op[K_MTYPE], 0, n_types - 1)]
        w_ctr = jnp.where(is_multi_op, g_ctr, wnm[:, 0])
        w_rank = jnp.where(is_multi_op, g_rank, wnm[:, 1])
        w_action = jnp.where(is_multi_op, g_action, wnm[:, 2])
        w_attr = jnp.where(is_multi_op, g_attr, wnm[:, 3])
        has_winner = jnp.where(is_multi_op, g_has, wnm[:, 0] >= 0)

        op_rank = ranks[op[K_ACT]]
        changed = _changed_vs_winner(
            op, op_rank, w_ctr, w_rank, w_action, w_attr, has_winner
        )

        new_st = _apply_mark_ctx(st, op, ctx)
        bnd_def = jnp.where(valid, new_st.bnd_def, bnd_def)
        bnd_mask = jnp.where(valid, new_st.bnd_mask, bnd_mask)

        # Cache maintenance mirrors _apply_mark's write classes: written
        # slots take their carry's winners, with the op merged into its own
        # type's entry where its bit lands (in-range) and it beats the
        # carried winner.  allowMultiple ops join rows but never affect a
        # per-type LWW entry.
        in_range = during
        write = written
        t_oh = jnp.arange(n_types, dtype=jnp.int32) == op[K_MTYPE]
        beats_nm = (wnm[:, 0] < 0) | (op[K_CTR] > wnm[:, 0]) | (
            (op[K_CTR] == wnm[:, 0]) & (op_rank > wnm[:, 1])
        )
        op_vals = jnp.stack(
            [op[K_CTR], op_rank, op[K_MACTION], op[K_MATTR]]
        ).astype(jnp.int32)
        upd = jnp.where(
            t_oh[None, :, None]
            & ((~is_multi_op) & in_range & beats_nm)[:, None, None],
            op_vals[None, None, :],
            wc_carry,
        )
        wcache = jnp.where((write & valid)[:, None, None], upd, wcache)

        rec = {
            "written": written & valid,
            "during": during & valid,
            "changed": changed & valid,
            "vis": vis,
            "obj_len": final_vis,
        }
        return (bnd_def, bnd_mask, acc, wcache), rec

    (bnd_def, bnd_mask, acc, wcache_f), mrec = lax.scan(
        step, (bnd_def0, bnd_mask0, acc0, wcache0), (mark_ops, m_idx0, mark_time)
    )
    # Inserts after every mark instant read the final planes.
    rows = bnd_mask[src_c]
    take = src_ok & (tm == mark_ops.shape[0])
    ins_mask = acc | jnp.where(take[:, None], rows, jnp.uint32(0))

    new_state = DocState(
        elem_ctr=elem_ctr,
        elem_act=elem_act,
        deleted=deleted,
        chars=chars,
        bnd_def=bnd_def,
        bnd_mask=bnd_mask,
        mark_ctr=mark_ctr_f,
        mark_act=mark_act_f,
        mark_action=mark_action_f,
        mark_type=mark_type_f,
        mark_attr=mark_attr_f,
        length=length,
        mark_count=mark_count_f,
    )
    records = {
        "kind": kind_t,
        "tvalid": tvalid,
        "index0": index0,
        "ins_mask": ins_mask,
        "written": mrec["written"],
        "during": mrec["during"],
        "changed": mrec["changed"],
        "vis": mrec["vis"],
        "obj_len": mrec["obj_len"],
        # Post-batch winner cache, persisted by the universe so the next
        # patched merge skips the dominance init.
        "wcache": wcache_f,
    }
    return new_state, _finish_records(records, bnd_def)


@functools.lru_cache(maxsize=None)
def _merge_step_sorted_patched_batch(
    maxk: int,
    has_marks: bool,
    has_wcache: bool,
    mode: str,
    group_k: int | None,
    has_multi: bool,
    t_act: int | None,
    readback: str,
    span_cap: int,
    cand_cap: int,
):
    kw = dict(
        maxk=maxk, has_marks=has_marks, mode=mode, group_k=group_k,
        has_multi=has_multi, t_act=t_act, readback=readback, span_cap=span_cap,
        cand_cap=cand_cap,
    )
    if has_wcache:
        def call(st, t, ro, nr, m, rk, b, mu, tt, mt, wc):
            return merge_step_sorted_patched(
                st, t, ro, nr, m, rk, b, mu, tt, mt, wcache_in=wc, **kw
            )

        return jax.jit(
            jax.vmap(call, in_axes=(0, 0, 0, None, 0, None, 0, None, 0, 0, 0))
        )
    return jax.jit(
        jax.vmap(
            functools.partial(merge_step_sorted_patched, **kw),
            in_axes=(0, 0, 0, None, 0, None, 0, None, 0, 0),
        )
    )


def merge_step_sorted_patched_batch(
    states,
    text_ops,
    round_of,
    num_rounds,
    mark_ops,
    ranks,
    char_buf,
    multi,
    text_time,
    mark_time,
    maxk: int,
    has_marks: bool = True,
    wcache_in=None,
    mode: str = "delta",
    group_k: int | None = None,
    has_multi: bool = True,
    t_act: int | None = None,
    readback: str = "planes",
    span_cap: int = 8,
    cand_cap: int = 64,
):
    """Jitted batched entry point for the patch-emitting sorted merge.

    ``has_marks=False`` (static, from the encoded batch) compiles the
    mark-free fast path: no winner-cache init, no mark scan.
    ``wcache_in`` ([R, 2C, T, 4]) threads the persisted winner cache; when
    given, the marked path compiles WITHOUT the dominance init.
    ``mode`` selects the mark-row scan: "delta" (default — compact carry,
    full planes read/written once per launch) or "dense" (the full-plane
    carry variant, kept for A/B via PERITEXT_PATCH_PATH=dense).  Both emit
    byte-identical patch streams and states.  ``group_k``/``has_multi``/
    ``t_act`` are delta-only static specializations from the host's
    allowMultiple group census and mark-type registry (dense always
    compiles the full PATCH_GROUP_K / MAX_MARK_TYPES machinery); they are
    normalized here so dense mode keeps ONE jit cache entry.
    ``readback``/``span_cap`` select the record transfer format (see
    merge_step_sorted_patched): "compact" reads back [M, span_cap] run
    tables instead of the [M, 2C] mark planes.
    """
    if mode not in ("delta", "dense"):
        raise ValueError(f"unknown patched merge mode {mode!r}")
    if readback not in ("planes", "compact"):
        raise ValueError(f"unknown patch readback format {readback!r}")
    if mode == "dense" or not has_marks:
        group_k, has_multi, t_act = None, True, None
    if readback == "planes":
        span_cap = 8  # unused by the planes variant: keep ONE jit cache entry
    if readback == "planes" or not has_marks:
        cand_cap = 64  # unused by these variants: keep ONE jit cache entry
    fn = _merge_step_sorted_patched_batch(
        maxk, has_marks, wcache_in is not None, mode, group_k, has_multi, t_act,
        readback, span_cap, cand_cap,
    )
    args = [
        states, text_ops, round_of, jnp.int32(num_rounds), mark_ops, ranks,
        char_buf, multi, text_time, mark_time,
    ]
    if wcache_in is not None:
        args.append(wcache_in)
    return fn(*args)


def flatten_sources(state: DocState):
    """Per-element effective boundary bitset, for materialization.

    Tensorized getTextWithFormatting left-inheritance (peritext.ts:366-390):
    element i's marks change at its "before" slot if defined, else at the
    previous element's "after" slot; otherwise they carry from the left.
    Returns (mask [C, W], has_marks [C]): the resolved mark-op bitset per
    element (zeros/False where no boundary is in scope).
    """
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length
    before_def = state.bnd_def[0::2]
    after_def = state.bnd_def[1::2]
    prev_after_def = jnp.roll(after_def, 1) & (ar > 0)
    d_slot = jnp.where(
        before_def, 2 * ar, jnp.where(prev_after_def, 2 * ar - 1, jnp.int32(-1))
    )
    has = (d_slot >= 0) & live
    src_elem = lax.cummax(jnp.where(has, ar, jnp.int32(-1)))
    src_slot = jnp.where(src_elem >= 0, d_slot[jnp.maximum(src_elem, 0)], jnp.int32(-1))
    mask = jnp.where(
        (src_slot >= 0)[:, None], state.bnd_mask[jnp.maximum(src_slot, 0)], jnp.uint32(0)
    )
    return mask, src_slot >= 0


flatten_sources_jit = jax.jit(flatten_sources)
flatten_sources_batch = jax.jit(jax.vmap(flatten_sources))


def cursor_elem(state: DocState, index: jax.Array):
    """Element id (ctr, act) of the index-th visible element.

    Tensorized getListElementId without the tombstone-peek option
    (reference micromerge.ts:762-805; cursors use the plain form,
    micromerge.ts:465-472).  Returns (ctr, act, found).
    """
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    visible = (ar < state.length) & ~state.deleted
    rank = jnp.cumsum(visible.astype(jnp.int32)) - 1  # visible index per slot
    match = visible & (rank == index)
    i = jnp.argmax(match).astype(jnp.int32)
    return state.elem_ctr[i], state.elem_act[i], jnp.any(match)


def resolve_cursor_index(state: DocState, ctr: jax.Array, act: jax.Array):
    """Visible index of the element (ctr, act): count of visible elements
    before it (reference findListElement, micromerge.ts:731-755 — a deleted
    cursor target resolves to the position where it was)."""
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length
    match = live & (state.elem_ctr == ctr) & (state.elem_act == act)
    i = jnp.argmax(match).astype(jnp.int32)
    visible = live & ~state.deleted
    before = jnp.sum((ar < i) & visible).astype(jnp.int32)
    return before, jnp.any(match)


cursor_elem_jit = jax.jit(cursor_elem)
resolve_cursor_index_jit = jax.jit(resolve_cursor_index)
# Fleet variants: one launch resolves a cursor per replica.
cursor_elems_batch = jax.jit(jax.vmap(cursor_elem, in_axes=(0, 0)))
resolve_cursor_indices_batch = jax.jit(jax.vmap(resolve_cursor_index, in_axes=(0, 0, 0)))


def visible_elem_id(state: DocState, index: jax.Array, peek: jax.Array):
    """Element id of the index-th visible element, with the optional
    tombstone-peek rule for insert anchoring.

    Reference getListElementId (micromerge.ts:762-805): with ``peek``, look
    past the run of tombstones immediately following the target; if any of
    them carries a markOpsAfter boundary, anchor on the *last* such tombstone
    so new characters land after a non-growing span-end (motivating test:
    test/micromerge.ts:520-566).  Also reproduces the reference's falsy-zero
    quirk (micromerge.ts:794) — harmless here because the peek run starts
    strictly after a visible element.
    """
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = ar < state.length
    visible = live & ~state.deleted
    rank = jnp.cumsum(visible.astype(jnp.int32)) - 1
    match = visible & (rank == index)
    i0 = jnp.argmax(match).astype(jnp.int32)
    found = jnp.any(match)

    first_vis_after = jnp.min(
        jnp.where(visible & (ar > i0), ar, jnp.int32(c))
    ).astype(jnp.int32)
    after_def = state.bnd_def[1::2]
    cand = live & state.deleted & (ar > i0) & (ar < first_vis_after) & after_def
    j_peek = jnp.max(jnp.where(cand, ar, jnp.int32(-1)))
    i = jnp.where(peek & (j_peek > 0), j_peek, i0)
    return state.elem_ctr[i], state.elem_act[i], found


visible_elem_id_jit = jax.jit(visible_elem_id)
visible_elem_ids_batch = jax.jit(jax.vmap(visible_elem_id, in_axes=(None, 0, None)))


def visible_length(state: DocState) -> jax.Array:
    ar = jnp.arange(state.capacity, dtype=jnp.int32)
    return jnp.sum((ar < state.length) & ~state.deleted).astype(jnp.int32)


visible_length_jit = jax.jit(visible_length)


def expand_mask_bits(mask: jax.Array, max_mark_ops: int) -> jax.Array:
    """[*, W] uint32 bitset rows -> [*, M] bool membership matrix."""
    m_idx = jnp.arange(max_mark_ops, dtype=jnp.int32)
    words = mask[..., m_idx // MASK_WORD_BITS]
    return ((words >> (m_idx % MASK_WORD_BITS).astype(jnp.uint32)) & 1).astype(bool)


def resolve_winners(state: DocState, present: jax.Array, ranks: jax.Array, multi: jax.Array) -> jax.Array:
    """LWW/multiset resolution of mark-op sets (reference opsToMarks,
    peritext.ts:294-326), as a dominance matmul.

    ``present[e, m]`` says mark op m is in element e's effective boundary set.
    Op m is *dominated* by m' when both address the same resolution group —
    same mark type for LWW marks, same (type, attr) for allowMultiple marks
    (comments resolve per comment id) — and m' has the greater op id.  The
    winners at an element are the present ops with no present dominator:
    a [C, M] x [M, M] masked matmul, which XLA maps onto the MXU.

    Returns winners [C, M] bool.  Effective marks follow directly: a winner
    with action addMark activates (type, attrs); a removeMark winner means
    the mark is absent.
    """
    is_multi = multi[state.mark_type]
    same_type = state.mark_type[:, None] == state.mark_type[None, :]
    same_attr = state.mark_attr[:, None] == state.mark_attr[None, :]
    same_group = same_type & (~is_multi[:, None] | same_attr)
    rank = ranks[state.mark_act]
    key_gt = (state.mark_ctr[None, :] > state.mark_ctr[:, None]) | (
        (state.mark_ctr[None, :] == state.mark_ctr[:, None])
        & (rank[None, :] > rank[:, None])
    )
    m_live = jnp.arange(state.max_mark_ops, dtype=jnp.int32) < state.mark_count
    dom = same_group & key_gt & m_live[None, :]  # dom[m, m']: m' dominates m
    dom_count = jnp.einsum(
        "em,nm->en", present.astype(jnp.float32), dom.astype(jnp.float32)
    )
    return present & (dom_count < 0.5) & m_live[None, :]


def convergence_digest(state: DocState, ranks: jax.Array, multi: jax.Array) -> jax.Array:
    """Order-sensitive checksum of the visible document + resolved marks.

    The TPU-native analog of the fuzzer's cross-replica convergence asserts
    (fuzz.ts:277-278): replicas that converged have equal digests, so a batch
    of replica pairs is convergence-checked with one vectorized compare (and
    across shards with a collective reduce).  Hashes *resolved* mark content
    (type/action/attr of winner ops), never table indices or bitset layout,
    because convergent replicas may hold the same ops at different table
    slots.
    """
    c = state.capacity
    ar = jnp.arange(c, dtype=jnp.int32)
    live = (ar < state.length) & ~state.deleted
    liveu = live.astype(jnp.uint32)
    vis_rank = (jnp.cumsum(liveu) - liveu) * liveu  # 0-based visible index
    mask, _ = flatten_sources(state)
    present = expand_mask_bits(mask, state.max_mark_ops)
    winners = resolve_winners(state, present, ranks, multi)
    adds = winners & (state.mark_action[None, :] == 0)
    mark_value = (
        state.mark_type.astype(jnp.uint32) * jnp.uint32(1000003)
        + (state.mark_attr + 1).astype(jnp.uint32) * jnp.uint32(8191)
        + jnp.uint32(17)
    )
    char_mix = jnp.sum((state.chars.astype(jnp.uint32) * jnp.uint32(2654435761) + vis_rank) * liveu)
    mark_mix = jnp.sum(
        adds.astype(jnp.uint32) * mark_value[None, :] * (vis_rank[:, None] * jnp.uint32(31) + 7) * liveu[:, None]
    )
    return jnp.uint32(2166136261) ^ char_mix ^ (mark_mix * jnp.uint32(31))


convergence_digest_batch = jax.jit(jax.vmap(convergence_digest, in_axes=(0, None, None)))


# ---------------------------------------------------------------------------
# Frontier-bounded window merge: device compute proportional to the edit
# ---------------------------------------------------------------------------
#
# Equivalence argument (mirroring the sorted-merge proof above).  Let W =
# [lo, hi] be a contiguous element range of the committed document such that
# for every op in the batch:
#
#   (i)   every insert's reference element and its ENTIRE skip run — the
#         contiguous post-reference run of elements whose op ids exceed the
#         *smallest* batch insert id (micromerge.ts:630-635) — lie in W, so
#         every placement position t satisfies lo <= t <= hi+1, and t = hi+1
#         only when hi is the last element of the document;
#   (ii)  every delete's target lies in W;
#   (iii) every mark op's start/end anchor slots, every DEFINED boundary
#         slot inside its [start, end) walk range, and the nearest defined
#         slot at or left of each anchor (the walk's carried currentOps
#         source, peritext.ts:181-186) lie in W's slot range;
#   (iv)  every insert's inherited-marks source (the nearest slot defined
#         at the insert's instant strictly left of its gap,
#         peritext.ts:328-330) lies in W's slot range.
#
# Then the merge restricted to the gathered window state — W's element and
# boundary rows with length = |W|, plus the full (small) mark table — equals
# the full-table merge restricted to W, and slots outside W are untouched by
# the full-table merge: placement reads only (i)'s rows (the skip-run stop
# rule never looks past the first non-skippable element, and window-local
# positions are global positions minus lo); tombstoning writes only (ii)'s
# rows; the mark walk reads/writes only (iii)'s slots, because in-range
# writes require definedness and anchor writes copy (iii)'s carry rows; and
# insert-row inheritance reads only (iv).  Scattering the merged window back
# over [lo, hi] with the tail shifted by the insert count therefore
# reproduces the full-table result exactly — states, patch records (with
# the vis_base/vis_after re-anchoring), and winner-cache rows alike.
# Visible indices decompose as global = local + vis_base because W is
# contiguous and all visibility changes happen inside W.
#
# The window conditions are computed HOST-side from the universe's causal
# mirror (ops/window.py); _window_ok re-verifies the membership conditions
# on device against the gathered window and the batch itself, so a stale or
# buggy census degrades to a full-table relaunch instead of corruption.


def _gather_window(state: DocState, start, hull_len, w_cap: int) -> DocState:
    """Slice a contiguous element window [start, start + w_cap) out of a
    replica state as a self-contained DocState of capacity ``w_cap``.

    ``length`` = ``hull_len`` (the census hull), so gathered slots beyond
    the hull — present only because w_cap is pow2-bucketed — read as dead
    padding to every kernel.  The mark table is small and rides whole.
    ``start`` must satisfy ``start + w_cap <= capacity`` (the host census
    clamps; dynamic_slice would silently re-anchor otherwise)."""
    s = jnp.int32(start)

    def win(p):
        return lax.dynamic_slice_in_dim(p, s, w_cap)

    return DocState(
        elem_ctr=win(state.elem_ctr),
        elem_act=win(state.elem_act),
        deleted=win(state.deleted),
        chars=win(state.chars),
        bnd_def=lax.dynamic_slice_in_dim(state.bnd_def, 2 * s, 2 * w_cap),
        bnd_mask=lax.dynamic_slice_in_dim(state.bnd_mask, 2 * s, 2 * w_cap, axis=0),
        mark_ctr=state.mark_ctr,
        mark_act=state.mark_act,
        mark_action=state.mark_action,
        mark_type=state.mark_type,
        mark_attr=state.mark_attr,
        length=jnp.int32(hull_len),
        mark_count=state.mark_count,
    )


def _scatter_window(state: DocState, win: DocState, start, hull_len) -> DocState:
    """Splice a merged window back into the full-capacity state.

    Elements [0, start) keep their rows, [start, start + win.length) come
    from the window, and the pre-batch tail shifts right by the insert
    count (win.length - hull_len).  Slots at or beyond the new length are
    masked to the dead-slot fills — the same convention the placement's
    shift splice leaves behind — so a windowed and a full-table merge of the same batch
    produce byte-identical planes."""
    c = state.capacity
    w_cap = win.capacity
    start = jnp.int32(start)
    shift = win.length - jnp.int32(hull_len)
    new_n = state.length + shift
    ar = jnp.arange(c, dtype=jnp.int32)
    in_win = (ar >= start) & (ar < start + win.length)
    win_idx = jnp.clip(ar - start, 0, w_cap - 1)
    old_idx = jnp.clip(jnp.where(ar < start, ar, ar - shift), 0, c - 1)

    def mix(old, winp, fill):
        v = jnp.where(in_win, winp[win_idx], old[old_idx])
        return jnp.where(ar < new_n, v, fill)

    ar2 = jnp.arange(2 * c, dtype=jnp.int32)
    in_win2 = (ar2 >= 2 * start) & (ar2 < 2 * start + 2 * win.length)
    win_idx2 = jnp.clip(ar2 - 2 * start, 0, 2 * w_cap - 1)
    old_idx2 = jnp.clip(jnp.where(ar2 < 2 * start, ar2, ar2 - 2 * shift), 0, 2 * c - 1)
    live2 = ar2 < 2 * new_n
    bnd_def = jnp.where(
        live2, jnp.where(in_win2, win.bnd_def[win_idx2], state.bnd_def[old_idx2]), False
    )
    bnd_mask = jnp.where(
        live2[:, None],
        jnp.where(
            in_win2[:, None], win.bnd_mask[win_idx2], state.bnd_mask[old_idx2]
        ),
        jnp.uint32(0),
    )
    return DocState(
        elem_ctr=mix(state.elem_ctr, win.elem_ctr, 0),
        elem_act=mix(state.elem_act, win.elem_act, 0),
        deleted=mix(state.deleted, win.deleted, False),
        chars=mix(state.chars, win.chars, 0),
        bnd_def=bnd_def,
        bnd_mask=bnd_mask,
        mark_ctr=win.mark_ctr,
        mark_act=win.mark_act,
        mark_action=win.mark_action,
        mark_type=win.mark_type,
        mark_attr=win.mark_attr,
        length=new_n,
        mark_count=win.mark_count,
    )


def _window_ok(win0: DocState, text_ops, mark_ops, w_cap: int):
    """Device-side soundness check of the host window census.

    Verifies, against the PRE-merge gathered window, the membership half of
    the window conditions: every text op's reference (HEAD, a window
    element, or a batch-created element), every mark anchor likewise, and
    that the window has room for the batch's inserts.  A False verdict
    makes the universe discard the windowed result and relaunch the
    full-table path — the adaptive always-correct fallback.  (The skip-run
    bound (i) is not re-checkable from the window alone; it holds because
    the census computes it from the mirror, which is itself a readback of
    committed device state.)"""
    ln = win0.length
    live = jnp.arange(w_cap, dtype=jnp.int32) < ln
    kind = text_ops[:, K_KIND]
    is_ins = (kind == KIND_INSERT) | (kind == KIND_INSERT_RUN)
    is_del = kind == KIND_DELETE
    k = jnp.where(kind == KIND_INSERT_RUN, text_ops[:, K_RUN_LEN], 1) * is_ins.astype(
        jnp.int32
    )

    def found_in_win(qc, qa):
        return jnp.any(
            live[None, :]
            & (win0.elem_ctr[None, :] == qc[:, None])
            & (win0.elem_act[None, :] == qa[:, None]),
            axis=1,
        )

    def found_in_batch(qc, qa):
        return jnp.any(
            is_ins[None, :]
            & (qa[:, None] == text_ops[None, :, K_ACT])
            & (qc[:, None] >= text_ops[None, :, K_CTR])
            & (qc[:, None] < text_ops[None, :, K_CTR] + k[None, :]),
            axis=1,
        )

    ref_ctr = text_ops[:, K_REF_CTR]
    ref_act = text_ops[:, K_REF_ACT]
    is_head = (ref_ctr == 0) & (ref_act == 0)
    ref_ok = found_in_win(ref_ctr, ref_act) | found_in_batch(ref_ctr, ref_act)
    text_ok = jnp.all(~(is_ins | is_del) | jnp.where(is_ins, is_head | ref_ok, ref_ok))

    mvalid = mark_ops[:, K_KIND] == KIND_MARK
    s_ok = found_in_win(mark_ops[:, K_SCTR], mark_ops[:, K_SACT]) | found_in_batch(
        mark_ops[:, K_SCTR], mark_ops[:, K_SACT]
    )
    e_ok = (
        (mark_ops[:, K_EKIND] == 2)
        | found_in_win(mark_ops[:, K_ECTR], mark_ops[:, K_EACT])
        | found_in_batch(mark_ops[:, K_ECTR], mark_ops[:, K_EACT])
    )
    mark_ok = jnp.all(~mvalid | (s_ok & e_ok))
    fit_ok = ln + jnp.sum(k) <= w_cap
    return text_ok & mark_ok & fit_ok


def merge_step_sorted_windowed(
    state: DocState,
    start,
    hull_len,
    text_ops,
    round_of,
    num_rounds,
    mark_ops,
    ranks,
    char_buf,
    maxk: int,
    w_cap: int,
):
    """merge_step_sorted over a gathered window, scattered back.

    Returns ``(new_state, wrec)`` where wrec carries the device census
    verdict (``wok``) and the post-merge window planes (``w_ctr``/``w_act``/
    ``w_del``/``w_def``) the universe splices into its host mirror — so the
    mirror stays a readback of device truth with O(window) transfer.  On
    ``wok=False`` the returned state is meaningless and must be discarded
    (the caller relaunches the full-table path)."""
    win0 = _gather_window(state, start, hull_len, w_cap)
    wok = _window_ok(win0, text_ops, mark_ops, w_cap)
    new_win = merge_step_sorted(
        win0, text_ops, round_of, num_rounds, mark_ops, ranks, char_buf, maxk
    )
    new_state = _scatter_window(state, new_win, start, hull_len)
    wrec = {
        "wok": wok,
        "w_ctr": new_win.elem_ctr,
        "w_act": new_win.elem_act,
        "w_del": new_win.deleted,
        "w_def": new_win.bnd_def,
    }
    return new_state, wrec


@functools.lru_cache(maxsize=None)
def _merge_step_sorted_windowed_batch(maxk: int, w_cap: int):
    return jax.jit(
        jax.vmap(
            functools.partial(merge_step_sorted_windowed, maxk=maxk, w_cap=w_cap),
            in_axes=(0, 0, 0, 0, 0, None, 0, None, 0),
        )
    )


def merge_step_sorted_windowed_batch(
    states, starts, hull_lens, text_ops, round_of, num_rounds, mark_ops, ranks,
    char_buf, maxk: int, w_cap: int,
):
    fn = _merge_step_sorted_windowed_batch(maxk, w_cap)
    return fn(
        states, starts, hull_lens, text_ops, round_of, jnp.int32(num_rounds),
        mark_ops, ranks, char_buf,
    )


def _gather_wcache_window(wcache, start, w_cap: int):
    return lax.dynamic_slice_in_dim(wcache, 2 * jnp.int32(start), 2 * w_cap, axis=0)


def _scatter_wcache_window(wcache, win_rows, start, hull_len, win_len, old_len):
    """Boundary-slot scatter of updated window winner-cache rows back into
    the full [2C, T, 4] cache (same shift rule as _scatter_window; rows at
    or beyond the new length mask to the empty entry, matching what a
    fresh dominance init over zeroed rows produces)."""
    two_c = wcache.shape[0]
    w2 = win_rows.shape[0]
    start = jnp.int32(start)
    shift = jnp.int32(win_len) - jnp.int32(hull_len)
    new_n2 = 2 * (jnp.int32(old_len) + shift)
    ar2 = jnp.arange(two_c, dtype=jnp.int32)
    in_win = (ar2 >= 2 * start) & (ar2 < 2 * start + 2 * jnp.int32(win_len))
    win_idx = jnp.clip(ar2 - 2 * start, 0, w2 - 1)
    old_idx = jnp.clip(jnp.where(ar2 < 2 * start, ar2, ar2 - 2 * shift), 0, two_c - 1)
    empty = jnp.array([-1, -1, 0, 0], jnp.int32)
    v = jnp.where(in_win[:, None, None], win_rows[win_idx], wcache[old_idx])
    return jnp.where((ar2 < new_n2)[:, None, None], v, empty[None, None, :])


def merge_step_sorted_patched_windowed(
    state: DocState,
    start,
    hull_len,
    vis_base,
    vis_after,
    text_ops,
    round_of,
    num_rounds,
    mark_ops,
    ranks,
    char_buf,
    multi,
    text_time,
    mark_time,
    maxk: int,
    has_marks: bool = True,
    wcache_in: jax.Array | None = None,
    mode: str = "delta",
    group_k: int | None = None,
    has_multi: bool = True,
    t_act: int | None = None,
    readback: str = "planes",
    span_cap: int = 8,
    cand_cap: int = 64,
    w_cap: int = 256,
):
    """merge_step_sorted_patched over a gathered window, scattered back.

    Records come out on GLOBAL visible coordinates (the vis_base/vis_after
    re-anchoring runs before span compaction), so the host assemblers are
    oblivious to windowing; ``wcache_in`` here is the FULL persisted cache
    — its window rows ride the window merge and scatter back, so cache
    warmth survives windowed ingests.  wrec extras as in
    merge_step_sorted_windowed."""
    win0 = _gather_window(state, start, hull_len, w_cap)
    wok = _window_ok(win0, text_ops, mark_ops, w_cap)
    wc_win = (
        None if wcache_in is None else _gather_wcache_window(wcache_in, start, w_cap)
    )
    new_win, rec = merge_step_sorted_patched(
        win0,
        text_ops,
        round_of,
        num_rounds,
        mark_ops,
        ranks,
        char_buf,
        multi,
        text_time,
        mark_time,
        maxk,
        has_marks=has_marks,
        wcache_in=wc_win,
        mode=mode,
        group_k=group_k,
        has_multi=has_multi,
        t_act=t_act,
        readback=readback,
        span_cap=span_cap,
        cand_cap=cand_cap,
        vis_base=vis_base,
        vis_after=vis_after,
    )
    new_state = _scatter_window(state, new_win, start, hull_len)
    wc = rec.pop("wcache", None)
    if wcache_in is not None and wc is not None:
        rec["wcache"] = _scatter_wcache_window(
            wcache_in, wc, start, hull_len, new_win.length, state.length
        )
    rec["wok"] = wok
    rec["w_ctr"] = new_win.elem_ctr
    rec["w_act"] = new_win.elem_act
    rec["w_del"] = new_win.deleted
    rec["w_def"] = new_win.bnd_def
    return new_state, rec


@functools.lru_cache(maxsize=None)
def _merge_step_sorted_patched_windowed_batch(
    maxk: int,
    has_marks: bool,
    has_wcache: bool,
    mode: str,
    group_k: int | None,
    has_multi: bool,
    t_act: int | None,
    readback: str,
    span_cap: int,
    cand_cap: int,
    w_cap: int,
):
    kw = dict(
        maxk=maxk, has_marks=has_marks, mode=mode, group_k=group_k,
        has_multi=has_multi, t_act=t_act, readback=readback, span_cap=span_cap,
        cand_cap=cand_cap, w_cap=w_cap,
    )
    if has_wcache:

        def call(st, s, h, vb, va, t, ro, nr, m, rk, b, mu, tt, mt, wc):
            return merge_step_sorted_patched_windowed(
                st, s, h, vb, va, t, ro, nr, m, rk, b, mu, tt, mt,
                wcache_in=wc, **kw
            )

        return jax.jit(
            jax.vmap(
                call,
                in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0, None, 0, None, 0, 0, 0),
            )
        )
    return jax.jit(
        jax.vmap(
            functools.partial(merge_step_sorted_patched_windowed, **kw),
            in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0, None, 0, None, 0, 0),
        )
    )


def merge_step_sorted_patched_windowed_batch(
    states,
    starts,
    hull_lens,
    vis_base,
    vis_after,
    text_ops,
    round_of,
    num_rounds,
    mark_ops,
    ranks,
    char_buf,
    multi,
    text_time,
    mark_time,
    maxk: int,
    w_cap: int,
    has_marks: bool = True,
    wcache_in=None,
    mode: str = "delta",
    group_k: int | None = None,
    has_multi: bool = True,
    t_act: int | None = None,
    readback: str = "planes",
    span_cap: int = 8,
    cand_cap: int = 64,
):
    """Jitted batched entry for the windowed patch-emitting sorted merge
    (same static-arg normalization as merge_step_sorted_patched_batch)."""
    if mode not in ("delta", "dense"):
        raise ValueError(f"unknown patched merge mode {mode!r}")
    if readback not in ("planes", "compact"):
        raise ValueError(f"unknown patch readback format {readback!r}")
    if mode == "dense" or not has_marks:
        group_k, has_multi, t_act = None, True, None
    if readback == "planes":
        span_cap = 8
    if readback == "planes" or not has_marks:
        cand_cap = 64
    fn = _merge_step_sorted_patched_windowed_batch(
        maxk, has_marks, wcache_in is not None, mode, group_k, has_multi, t_act,
        readback, span_cap, cand_cap, w_cap,
    )
    args = [
        states, starts, hull_lens, vis_base, vis_after, text_ops, round_of,
        jnp.int32(num_rounds), mark_ops, ranks, char_buf, multi, text_time,
        mark_time,
    ]
    if wcache_in is not None:
        args.append(wcache_in)
    return fn(*args)


@jax.jit
def window_reach_bound(elem_ctr, elem_act, length, ids, head, inserts):
    """Lower bound on the window census's largest ``hull + inserts``, read
    from committed state without the mirror (ops/window.py,
    ``reach_queries`` / ``reach_rejects``).

    ``ids`` [R, K, 2] are (ctr, act) pairs replica r's batch looks up in
    its table (-1 pads match nothing), ``head`` [R] marks a batch that
    inserts at position 0, ``inserts`` [R] its insert count.  The census
    hull holds every id it finds, so it spans at least the smallest to the
    largest of their positions.  Returns the fleet-wide maximum of that
    span plus the replica's inserts: one int32."""
    cap = elem_ctr.shape[-1]
    pos = jnp.arange(cap, dtype=jnp.int32)
    live = pos < length[:, None]
    hit = (
        live[:, None, :]
        & (elem_ctr[:, None, :] == ids[:, :, 0:1])
        & (elem_act[:, None, :] == ids[:, :, 1:2])
    )
    # Ids are unique in a table: one reduction per id gives its position
    # (or -1), and the [R, K, C] compare never leaves the fusion.
    at = jnp.max(jnp.where(hit, pos, -1), axis=-1)
    lo = jnp.min(jnp.where(at >= 0, at, cap), axis=-1)
    hi = jnp.max(at, axis=-1)
    lo = jnp.where(head, 0, lo)
    hi = jnp.where(head, jnp.maximum(hi, 0), hi)
    span = jnp.where(hi >= lo, hi - lo + 1, 0)
    return jnp.max(span + inserts)
