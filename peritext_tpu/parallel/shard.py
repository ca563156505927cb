"""Explicit sequence-parallel kernels with shard_map + manual collectives.

GSPMD already shards the jnp kernels over the ``seq`` axis automatically
(parallel/mesh.py); this module is the *explicit* formulation — the document
dimension split across chips with hand-placed collectives over ICI — for the
long-document regime where one replica's sequence spans a slice:

- the mark-inheritance carry (``getTextWithFormatting``'s left-to-right
  walk, peritext.ts:366-390) becomes: a local prefix resolution per shard, a
  one-element **halo exchange** to the right neighbor (``ppermute`` ring
  shift) for the after-slot of each shard's last element, and a shard-level
  prefix over "last defined boundary per shard" summaries (``all_gather``
  along the seq axis — S summaries of W words each, a few hundred bytes on
  the wire, vs. the O(C) state that stays put).

The result is bit-identical to the single-device ``flatten_sources``
(tests/test_shard_map.py) while the per-shard work and memory scale as C/S.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _row_at(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """rows[idx] for a traced idx, safe for idx = -1 (returns zeros)."""
    safe = jnp.maximum(idx, 0)
    row = lax.dynamic_slice_in_dim(rows, safe, 1, axis=0)[0]
    return jnp.where(idx >= 0, row, jnp.zeros_like(row))


def _sharded_flatten_local(
    elem_deleted, bnd_def, bnd_mask, length, *, seq_size: int
):
    """Per-shard body: local resolution + halo + shard-prefix carry.

    Operates on one replica's local slice (c_local elements).  Uses the
    ``seq`` axis name for collectives.
    """
    c_local = elem_deleted.shape[0]
    shard = lax.axis_index("seq")
    elem_offset = shard * c_local
    ar_local = jnp.arange(c_local, dtype=jnp.int32)
    ar_global = elem_offset + ar_local
    live = ar_global < length

    before_def = bnd_def[0::2] & live
    after_def = bnd_def[1::2] & live
    before_rows = bnd_mask[0::2]
    after_rows = bnd_mask[1::2]

    # Halo exchange: each shard sends its last element's (after_def, after
    # row) to its right neighbor over the ICI ring; shard 0 receives zeros.
    perm = [(s, s + 1) for s in range(seq_size - 1)]
    halo_def = lax.ppermute(after_def[-1], "seq", perm)
    halo_row = lax.ppermute(after_rows[-1], "seq", perm)

    prev_after_def = jnp.concatenate([halo_def[None], after_def[:-1]])
    prev_after_rows = jnp.concatenate([halo_row[None], after_rows[:-1]])

    # Element-level decision (reference peritext.ts:372-376): own before
    # slot wins, else the previous element's after slot.
    d_has = before_def | prev_after_def
    d_rows = jnp.where(before_def[:, None], before_rows, prev_after_rows)

    # Local prefix: nearest deciding element at or left of each element.
    src = lax.cummax(jnp.where(d_has, ar_local, jnp.int32(-1)))
    local_rows = jax.vmap(lambda i: _row_at(d_rows, i))(src)
    local_has = src >= 0

    # Shard summary: this shard's last deciding row (if any), gathered
    # across the seq axis so each shard can take the nearest preceding one.
    last_idx = jnp.max(jnp.where(d_has, ar_local, jnp.int32(-1)))
    summary_row = _row_at(d_rows, last_idx)
    summary_has = last_idx >= 0
    all_rows = lax.all_gather(summary_row, "seq")  # [S, W]
    all_has = lax.all_gather(summary_has, "seq")  # [S]

    s_idx = jnp.arange(seq_size, dtype=jnp.int32)
    prev_shards = all_has & (s_idx < shard)
    pick = jnp.max(jnp.where(prev_shards, s_idx, jnp.int32(-1)))
    incoming_row = _row_at(all_rows, pick)
    incoming_has = pick >= 0

    mask = jnp.where(local_has[:, None], local_rows, incoming_row[None, :])
    has = local_has | incoming_has
    return mask, has


def _placement_round_local(carry, r, text_ops, round_of, ranks, char_buf,
                           *, halo: int, maxk: int, c_global: int, seq_size: int):
    """One sort-based placement round on this shard's slice of one replica.

    The sharded form of kernels._place_round: anchor resolution and the
    skip-run stop become local candidates reduced with ``lax.pmin`` over the
    ``seq`` ICI ring ([L]-sized messages); block ordering is replicated [L]
    math; the splice is a left-neighbor **halo exchange** (``ppermute`` of
    the last ``halo`` elements — elements only ever shift right, by at most
    the round's total insert budget) followed by purely local scatters.
    """
    ec, ea, dl, ch, oi, ln = carry
    c_local = ec.shape[0]
    shard = lax.axis_index("seq")
    lo = shard * c_local
    gpos = lo + jnp.arange(c_local, dtype=jnp.int32)
    big = jnp.int32(2 * c_global + 2)
    K = _K()

    kind = text_ops[:, K.K_KIND]
    active = round_of == r
    is_ins = active & ((kind == K.KIND_INSERT) | (kind == K.KIND_INSERT_RUN))
    is_run = kind == K.KIND_INSERT_RUN
    is_del = active & (kind == K.KIND_DELETE)
    alive = gpos < ln

    ref_ctr = text_ops[:, K.K_REF_CTR]
    ref_act = text_ops[:, K.K_REF_ACT]
    ref_match = (
        alive[None, :] & (ec[None, :] == ref_ctr[:, None]) & (ea[None, :] == ref_act[:, None])
    )  # [L, Cl]

    # Deletes are shard-local.
    dl = dl | (ref_match & is_del[:, None]).any(axis=0)

    # Reference element position: local min-candidate -> pmin over the ring.
    local_first = jnp.min(jnp.where(ref_match, gpos[None, :], big), axis=1)
    global_first = lax.pmin(local_first, "seq")  # [L]
    is_head = (ref_ctr == 0) & (ref_act == 0)
    # The unsharded path's argmax(all-False) == 0 fallback, reproduced.
    idx = jnp.where(is_head, jnp.int32(-1), jnp.where(global_first >= big, 0, global_first))

    # Skip-run stop: same local-candidate + pmin shape.
    ctr_i = text_ops[:, K.K_CTR]
    rank_i = ranks[text_ops[:, K.K_ACT]]
    elem_rank = ranks[ea]
    gt = (ec[None, :] > ctr_i[:, None]) | (
        (ec[None, :] == ctr_i[:, None]) & (elem_rank[None, :] > rank_i[:, None])
    )
    stop = (gpos[None, :] > idx[:, None]) & ~(alive[None, :] & gt)
    t_local = jnp.min(jnp.where(stop, gpos[None, :], big), axis=1)
    t = lax.pmin(t_local, "seq")
    t = jnp.where(t >= big, jnp.int32(c_global), t)

    # Block ordering: replicated [L]/[L, L] math, identical on every shard.
    k = jnp.where(is_run, text_ops[:, K.K_RUN_LEN], 1) * is_ins.astype(jnp.int32)
    id_gt = (ctr_i[None, :] > ctr_i[:, None]) | (
        (ctr_i[None, :] == ctr_i[:, None]) & (rank_i[None, :] > rank_i[:, None])
    )
    before = (t[None, :] < t[:, None]) | ((t[None, :] == t[:, None]) & id_gt)
    s = t + jnp.sum(k[None, :] * before.astype(jnp.int32), axis=1)

    # Halo exchange: elements only move rightward, by at most the round's
    # insert budget (<= halo), so each shard needs the ceil(halo / Cl)
    # whole shards to its left as splice sources — one ppermute hop per
    # shard-width of displacement.  Left-edge shards receive zeros for
    # hops that fall off the ring; those positions mask out via src_gpos.
    hops = min(-(-halo // c_local), seq_size - 1) if seq_size > 1 else 0
    region = hops * c_local

    def halo_of(x):
        parts = [
            lax.ppermute(x, "seq", [(i, i + d) for i in range(seq_size - d)])
            for d in range(hops, 0, -1)
        ]
        return jnp.concatenate(parts) if parts else x[:0]

    def splice_into_local(own, halo_vals, fill, block_vals):
        src = jnp.concatenate([halo_vals, own])  # [region + Cl]
        src_gpos = lo - region + jnp.arange(region + c_local, dtype=jnp.int32)
        src_ok = (src_gpos >= 0) & (src_gpos < ln)
        shift = jnp.sum(
            k[:, None] * (t[:, None] <= src_gpos[None, :]).astype(jnp.int32), axis=0
        )
        # Out-of-shard destinations park at c_local; NOTE negative indices
        # must be clamped explicitly — .at[] applies Python negative-index
        # wrapping before drop-mode bounds checking.
        dest_local = src_gpos + shift - lo
        dest_local = jnp.where(
            src_ok & (dest_local >= 0), dest_local, jnp.int32(c_local)
        )
        out = jnp.full(c_local, fill, own.dtype)
        out = out.at[dest_local].set(src, mode="drop")
        # Op blocks: replicated values, locally-intersected destinations.
        off = jnp.arange(maxk, dtype=jnp.int32)
        in_block = off[None, :] < k[:, None]
        dest_ops = s[:, None] + off[None, :] - lo
        dest_ops = jnp.where(
            in_block & (dest_ops >= 0), dest_ops, jnp.int32(c_local)
        )
        return out.at[dest_ops].set(block_vals, mode="drop")

    off = jnp.arange(maxk, dtype=jnp.int32)
    buf_idx = jnp.clip(text_ops[:, K.K_PAYLOAD, None] + off[None, :], 0, char_buf.shape[0] - 1)
    block_chars = jnp.where(is_run[:, None], char_buf[buf_idx], text_ops[:, K.K_PAYLOAD, None])
    block_ctr = ctr_i[:, None] + off[None, :]
    block_act = jnp.broadcast_to(text_ops[:, K.K_ACT, None], block_ctr.shape)
    zero_blk = jnp.zeros_like(block_ctr)

    new_carry = (
        splice_into_local(ec, halo_of(ec), 0, block_ctr),
        splice_into_local(ea, halo_of(ea), 0, block_act),
        splice_into_local(dl.astype(jnp.int32), halo_of(dl.astype(jnp.int32)), 0, zero_blk).astype(bool),
        splice_into_local(ch, halo_of(ch), 0, block_chars),
        splice_into_local(oi, halo_of(oi), -1, zero_blk - 1),
        ln + jnp.sum(k),
    )
    return new_carry


def _K():
    from peritext_tpu.ops import kernels

    return kernels


def place_text_sp(mesh: Mesh, halo: int, maxk: int):
    """shard_map-compiled sequence-parallel sort-based text placement.

    The explicit-collective long-document form of kernels.place_text_batch:
    per-shard work and memory scale as C/S while the cross-shard traffic is
    [L]-sized pmin reductions plus ceil(halo / (C/S)) shard-wide ppermute
    pulls per round.  ``halo`` must be >= the largest single-round insert
    budget (the caller buckets the batch's total inserted characters);
    displacements wider than a shard resolve through multi-hop pulls, up
    to the whole ring.  Returns a jitted fn mapping the batched element
    arrays + op tensors to (ec, ea, dl, ch, oi, length).
    """
    seq_size = mesh.shape["seq"]

    def per_replica(ec, ea, dl, ch, ln, text_ops, round_of, num_rounds, ranks, char_buf):
        c_local = ec.shape[0]
        shard = lax.axis_index("seq")
        oi = shard * c_local + jnp.arange(c_local, dtype=jnp.int32)
        # The initial orig-idx plane is seq-varying only; the loop mixes it
        # with replica-varying data, so align its varying axes up front.
        oi = lax.pcast(oi, ("replica",), to="varying")
        carry = (ec, ea, dl, ch, oi, ln)
        carry = lax.fori_loop(
            0,
            num_rounds,
            lambda r, cry: _placement_round_local(
                cry, r, text_ops, round_of, ranks, char_buf,
                halo=halo, maxk=maxk, c_global=c_local * seq_size, seq_size=seq_size,
            ),
            carry,
        )
        return carry

    def batched(ec, ea, dl, ch, ln, text_ops, round_of, num_rounds, ranks, char_buf):
        return jax.vmap(
            per_replica, in_axes=(0, 0, 0, 0, 0, 0, 0, None, None, 0)
        )(ec, ea, dl, ch, ln, text_ops, round_of, num_rounds, ranks, char_buf)

    mapped = shard_map(
        batched,
        mesh=mesh,
        in_specs=(
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica"),
            P("replica", None, None),
            P("replica", None),
            P(),
            P(),
            P("replica", None),
        ),
        out_specs=(
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica"),
        ),
    )
    return jax.jit(mapped)


def merge_step_sorted_sp(mesh: Mesh, halo: int, maxk: int):
    """Full sorted merge for the long-document regime: explicit-collective
    text placement (place_text_sp) composed with the GSPMD-sharded tail
    (boundary permute + batched mark phase — gathers and [2C, M] matmuls
    that GSPMD partitions over the same mesh).  State-equivalent to
    kernels.merge_step_sorted on the gathered arrays.
    """
    K = _K()
    place = place_text_sp(mesh, halo=halo, maxk=maxk)

    def step(states, text_ops, round_of, num_rounds, mark_ops, ranks, char_buf):
        ec, ea, dl, ch, oi, ln = place(
            states.elem_ctr,
            states.elem_act,
            states.deleted,
            states.chars,
            states.length,
            text_ops,
            round_of,
            num_rounds,
            ranks,
            char_buf,
        )
        bnd_def, bnd_mask = jax.vmap(K._permute_boundaries)(
            states.bnd_def, states.bnd_mask, oi
        )
        return jax.vmap(K._sorted_tail)(
            states, ec, ea, dl.astype(bool), ch, ln, bnd_def, bnd_mask, mark_ops
        )

    return jax.jit(step)


def flatten_sources_sp(mesh: Mesh):
    """shard_map-compiled sequence-parallel flatten over (replica, seq).

    Takes the batched raw arrays (deleted [R, C], bnd_def [R, 2C],
    bnd_mask [R, 2C, W], length [R]) and returns (mask [R, C, W],
    has [R, C]) identical to jax.vmap(kernels.flatten_sources).
    """
    seq_size = mesh.shape["seq"]

    def per_replica(deleted, bnd_def, bnd_mask, length):
        return _sharded_flatten_local(
            deleted, bnd_def, bnd_mask, length, seq_size=seq_size
        )

    def batched(deleted, bnd_def, bnd_mask, length):
        return jax.vmap(per_replica)(deleted, bnd_def, bnd_mask, length)

    mapped = shard_map(
        batched,
        mesh=mesh,
        in_specs=(
            P("replica", "seq"),
            P("replica", "seq"),
            P("replica", "seq", None),
            P("replica"),
        ),
        out_specs=(P("replica", "seq", None), P("replica", "seq")),
    )
    return jax.jit(mapped)
