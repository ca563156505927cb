"""TpuUniverse: a batch of document replicas resident on device.

The deployment unit of the TPU engine.  A universe holds R replica states
stacked into one [R, ...] pytree, shares actor/attr interning across the
batch, and ingests causally-gated change batches with a single
jit(vmap(scan)) launch — the reference's applyChange path
(micromerge.ts:499-514) batched over replicas, which is the framework's
throughput axis (BASELINE.json north star).

Host responsibilities (the control plane): causal sorting and the
seq/deps gate per replica, wire-op encoding/interning, capacity pre-checks
with automatic re-bucketing, and span decoding for materialization.  Device
responsibilities (the data plane): all per-op document mutation, boundary-set
algebra, mark resolution, digests.
"""
from __future__ import annotations

import copy
import functools
import hashlib
import json
import logging
import math
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from peritext_tpu.ids import ActorRegistry, make_op_id, parse_op_id
from peritext_tpu.ops import kernels as K
from peritext_tpu.ops import window as W
from peritext_tpu.ops.encode import (
    AttrRegistry,
    TIME_PAD,
    bucket_length,
    encode_changes,
    pad_rows,
    prepare_sorted_batch,
    split_rows,
)
from peritext_tpu.ops.state import (
    DocState,
    grow_state,
    index_state,
    make_empty_state,
    stack_states,
)
from peritext_tpu.oracle.doc import (
    ListItem,
    ObjectStore,
    get_list_element_id,
    get_text_with_formatting as oracle_spans,
    op_from_wire,
    ops_to_marks,
)
from peritext_tpu.runtime import faults
from peritext_tpu.runtime import health
from peritext_tpu.runtime import telemetry
from peritext_tpu.runtime.sync import causal_order
from peritext_tpu import schema
from peritext_tpu.schema import allow_multiple_array

Change = Dict[str, Any]

_log = logging.getLogger(__name__)


class DeviceLaunchError(RuntimeError):
    """A device launch kept failing after the configured retry budget.

    ``__cause__`` / ``cause`` carry the last attempt's exception.  With
    degradation enabled (the default) callers never see this for ingest —
    the batch completes on the oracle CPU path instead.
    """

    def __init__(self, attempts: int, cause: Optional[BaseException]):
        super().__init__(
            f"device launch failed after {attempts} attempt(s): {cause!r}"
        )
        self.attempts = attempts
        self.cause = cause


def _launch_policy() -> Tuple[int, float, float]:
    """(retries, backoff base seconds, per-attempt deadline seconds).

    ``PERITEXT_LAUNCH_RETRIES`` extra attempts (default 2) with exponential
    backoff ``PERITEXT_LAUNCH_BACKOFF * 2**i`` (default 0.05s, capped at 2s).
    ``PERITEXT_LAUNCH_TIMEOUT`` > 0 adds a wall-clock deadline per attempt,
    enforced around the host readback barrier (subprocess-free: the attempt
    blocks on the readback, then the elapsed time is judged — a wedged
    backend surfaces as a late readback, which the policy counts as a
    failed attempt instead of committing behind it)."""
    return (
        int(os.environ.get("PERITEXT_LAUNCH_RETRIES", "2")),
        float(os.environ.get("PERITEXT_LAUNCH_BACKOFF", "0.05")),
        float(os.environ.get("PERITEXT_LAUNCH_TIMEOUT", "0")),
    )


def _degrade_enabled() -> bool:
    return os.environ.get("PERITEXT_DEGRADE", "1") != "0"


def _window_enabled() -> bool:
    """Frontier-bounded window merge gate (PERITEXT_MERGE_WINDOW).

    Default on; ``0`` pins the full-table path (the A/B baseline, and what
    the test-window-off CI leg runs the differential suites under)."""
    return os.environ.get("PERITEXT_MERGE_WINDOW", "1") != "0"


def _window_min_cap() -> int:
    """Smallest table capacity the windowed path engages at
    (PERITEXT_MERGE_WINDOW_MIN, default 512): below it the gather/scatter
    and census overhead dominate what the window saves."""
    raw = os.environ.get("PERITEXT_MERGE_WINDOW_MIN", "512")
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"PERITEXT_MERGE_WINDOW_MIN must be an integer, got {raw!r}"
        )
    if v < 1:
        raise ValueError(f"PERITEXT_MERGE_WINDOW_MIN must be >= 1, got {v}")
    return v


def _window_backoff() -> int:
    """Census-rejection backoff threshold (PERITEXT_WINDOW_BACKOFF,
    default 4; 0 disables): after this many CONSECUTIVE census passes that
    plan_windows rejected (hull too wide), the census — and with it the
    per-batch mirror-rebuild D2H that full-table commits force — is
    skipped for 2x-threshold batches before probing again.  Purely a cost
    valve: skipped batches take the byte-identical full-table path."""
    raw = os.environ.get("PERITEXT_WINDOW_BACKOFF", "4")
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"PERITEXT_WINDOW_BACKOFF must be an integer, got {raw!r}"
        )
    if v < 0:
        raise ValueError(f"PERITEXT_WINDOW_BACKOFF must be >= 0, got {v}")
    return v


def _patch_readback() -> str:
    """Record transfer format for the patch-emitting launches.

    "compact" (default): the mark patch planes reduce on device to
    [M, span_cap] run tables (kernels.compact_mark_records) and only those
    — plus the analytic text records — cross the D2H link, so readback
    bytes are proportional to the emitted patches, not the document.
    "planes" keeps the full [M, 2C] per-slot planes (the A/B baseline).
    Both assemble byte-identical patch streams.
    """
    mode = os.environ.get("PERITEXT_PATCH_READBACK", "compact")
    if mode not in ("compact", "planes"):
        raise ValueError(
            f"PERITEXT_PATCH_READBACK must be 'compact' or 'planes', got {mode!r}"
        )
    return mode


def _initial_span_cap() -> int:
    """Starting per-mark-row span capacity for the compact readback
    (PERITEXT_PATCH_SPAN_CAP, pow2-bucketed).  A mark op's emitted patch
    count is data-dependent — the host census cannot bound it — so the cap
    is adaptive instead: a launch whose true counts overflow it falls back
    to a planes readback for that batch (byte-identical stream either way)
    and the universe grows its cap so the steady state stops overflowing.
    """
    raw = os.environ.get("PERITEXT_PATCH_SPAN_CAP", "8")
    try:
        cap = int(raw)
    except ValueError:
        raise ValueError(f"PERITEXT_PATCH_SPAN_CAP must be an integer, got {raw!r}")
    if cap < 1:
        raise ValueError(f"PERITEXT_PATCH_SPAN_CAP must be >= 1, got {cap}")
    return bucket_length(cap, minimum=1)


# Once TpuUniverse.warm_patched has compiled a universe's patched
# programs ahead of traffic, every patched launch keeps to their shapes:
# the run-length cap pads to at least WARM_RUN (the first rung of encode's
# row bucket, which op and mark rows already pad to) and the char buffer to
# hold one char past its MAX_RUN_LEN tail; the marked and allowMultiple
# variants compile whatever the batch holds, with the compact readback's
# candidates sized by the mark table; windows are at least
# 1/WARM_WINDOW_SHARE of the table (so the full-table merge and at most
# three window widths at any capacity); and no winner cache persists.
WARM_RUN = bucket_length(1)
WARM_BUF = bucket_length(K.MAX_RUN_LEN + 1)
WARM_WINDOW_SHARE = 8


def _warm_narrowest(capacity: int) -> int:
    """The narrowest window a warmed universe's census picks."""
    return bucket_length(capacity // WARM_WINDOW_SHARE, minimum=W.MIN_WINDOW)


def _warm_windows(capacity: int) -> List[int]:
    """Every window width a warmed universe's census can pick at
    ``capacity`` (pow2 from :func:`_warm_narrowest` up to half the table);
    empty where the windowed merge does not engage."""
    if not _window_enabled() or capacity < _window_min_cap():
        return []
    if os.environ.get("PERITEXT_SORTED_CHUNK") or os.environ.get("PERITEXT_PATCH_CHUNK"):
        return []
    caps = []
    w = _warm_narrowest(capacity)
    while W.window_fits(w, capacity):
        caps.append(w)
        w *= 2
    return caps


def _codepoints_to_str(codepoints: np.ndarray) -> str:
    """Vectorized codepoint-array -> str (no per-char Python loop).

    surrogatepass: lone surrogates are representable in Python strings
    (they arrive via JS/JSON escapes and round-trip through ``chr()`` on
    the per-char assembly paths), so the batch decode must accept exactly
    what ``chr()`` accepts."""
    return codepoints.astype("<u4").tobytes().decode("utf-32-le", "surrogatepass")


def _decode_mask_row(
    row: np.ndarray,
    op_ids: List[str],
    table: Dict[str, Dict[str, Any]],
    cache: Dict[bytes, Dict[str, Any]],
) -> Dict[str, Any]:
    """Decode one boundary bitset row into an effective mark map via the
    oracle's ops_to_marks, memoized on the row bytes.  THE one decode
    shared by every patch assembler (bit unpacking and caching cannot
    drift between them).  Returns the CACHED dict — callers handing it to
    patch consumers must ``_copy_jsonlike`` it first."""
    key = row.tobytes()
    marks = cache.get(key)
    if marks is None:
        present = frozenset(
            op_id for m, op_id in enumerate(op_ids) if row[m // 32] >> (m % 32) & 1
        )
        marks = ops_to_marks(present, table)
        cache[key] = marks
    return marks


def _copy_jsonlike(x: Any) -> Any:
    """Cheap structural copy of JSON-shaped patch/mark values (dicts,
    lists, immutable scalars).  Equal by == to ``copy.deepcopy`` on these
    shapes at a fraction of the cost — deepcopy's memo/dispatch machinery
    dominated patch assembly when run once per inserted character and once
    per host patch per replica."""
    if isinstance(x, dict):
        return {k: _copy_jsonlike(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_copy_jsonlike(v) for v in x]
    return x


def _strip_pos(pairs: List[Any], with_positions: bool) -> List[Any]:
    """Finalize one replica's ``(pos, patch)`` stream: keep the pairs when
    the caller asked for positions (the serving plane's per-submission
    split), else strip to the plain patch list.  ``pairs`` must already be
    in stream (pos) order — every producer sorts or emits in order."""
    if with_positions:
        return list(pairs)
    return [p for _, p in pairs]


# Transient-failure classification (shared with the Editor's delivery
# buffer; see faults.retryable): transient errors retry, semantic errors
# propagate untouched.
_retryable = faults.retryable


_multi_cache: Dict[Tuple[bytes, Any], Any] = {}


def _multi_jax(sharding: Any = None):
    """Device-resident allowMultiple flag vector, re-uploaded only when the
    mark-type registry actually changes.  Per-ingest ``jnp.asarray`` of
    the freshly built numpy vector cost one device_put per launch — fixed
    overhead that dominates small windowed launches (PROFILE: ~0.1ms per
    transfer on the 1-core box)."""
    arr = allow_multiple_array()
    key = (arr.tobytes(), sharding)
    hit = _multi_cache.get(key)
    if hit is None:
        if len(_multi_cache) > 8:
            _multi_cache.clear()
        hit = _multi_cache[key] = jax.device_put(arr, sharding)
    return hit


def _blackbox_on_error(fn):
    """Black-box post-mortem on an unhandled ingest exception.

    A no-op unless ``PERITEXT_BLACKBOX`` is armed (telemetry.blackbox_dump
    checks and returns immediately).  :class:`DeviceLaunchError` is
    excluded — the retry machinery already dumped at budget exhaustion,
    and a second dump for the same failure would waste the per-process
    dump budget.  The exception always propagates unchanged.
    """

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        try:
            return fn(self, *args, **kwargs)
        except DeviceLaunchError:
            raise
        except Exception as exc:
            telemetry.blackbox_dump(
                "ingest_exception",
                method=fn.__name__,
                error=f"{type(exc).__name__}: {exc}",
            )
            raise

    return wrapper


def apply_host_op(store: ObjectStore, op: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Apply one wire-format structural/host-object op to a replica's host
    object store (the oracle's per-object dispatch, micromerge.ts:534-608).
    Returns the emitted patches.

    The device engine's data plane is the root text list; every other object
    — the root map, nested maps, second lists, comment tables — lives in the
    host :class:`ObjectStore`, which shares the oracle's exact semantics
    (map-key LWW, RGA list inserts, mark walks)."""
    return store.apply_op(op_from_wire(op))


def assemble_patches(
    records: Dict[str, np.ndarray],
    r: int,
    op_rows: np.ndarray,
    table: Dict[str, Dict[str, Any]],
    attrs: AttrRegistry,
    row_pos: Optional[np.ndarray] = None,
) -> List[Dict[str, Any]]:
    """Reference-format patches from per-op device records (one replica).

    With ``row_pos`` (the flat batch-stream position of each op row, from
    encode_changes), returns ``(pos, patch)`` pairs instead, so the caller
    can interleave device patches with host-object patches in op order.

    Consumes either record format: the full per-slot planes, or the
    compact run tables (``mstart``/``mend``/``mcount`` present) — in which
    case kind and the insert payload come from the host-side ``op_rows``
    (the kernel drops host-redundant fields from the compact readback)."""
    patches: List[Any] = []

    def emit(i: int, patch: Dict[str, Any]) -> None:
        patches.append(patch if row_pos is None else (int(row_pos[i]), patch))

    op_ids = list(table)
    mask_cache: Dict[bytes, Dict[str, Any]] = {}

    def decode_mask(row: np.ndarray) -> Dict[str, Any]:
        return _copy_jsonlike(_decode_mask_row(row, op_ids, table, mask_cache))

    compact = "mstart" in records
    num_ops = op_rows.shape[0] if compact else records["kind"].shape[1]
    for i in range(num_ops):
        kind = int(op_rows[i, K.K_KIND]) if compact else int(records["kind"][r, i])
        if kind == K.KIND_PAD or not records["valid"][r, i]:
            continue
        if kind == K.KIND_INSERT:
            char = (
                int(op_rows[i, K.K_PAYLOAD]) if compact else int(records["char"][r, i])
            )
            emit(
                i,
                {
                    "path": ["text"],
                    "action": "insert",
                    "index": int(records["index"][r, i]),
                    "values": [chr(char)],
                    "marks": decode_mask(records["ins_mask"][r, i]),
                },
            )
        elif kind == K.KIND_DELETE:
            emit(
                i,
                {
                    "path": ["text"],
                    "action": "delete",
                    "index": int(records["index"][r, i]),
                    "count": 1,
                },
            )
        elif kind == K.KIND_MARK:
            if compact:
                span_patches = _mark_span_patches(
                    records["mstart"][r, i],
                    records["mend"][r, i],
                    int(records["mcount"][r, i]),
                    op_rows[i],
                    attrs,
                )
            else:
                span_patches = assemble_mark_patches(records, r, i, op_rows[i], attrs)
            for patch in span_patches:
                emit(i, patch)
    return patches


def _mark_patch_list(
    written: np.ndarray,
    during: np.ndarray,
    changed: np.ndarray,
    vis: np.ndarray,
    obj_len: int,
    op_row: np.ndarray,
    attrs: AttrRegistry,
) -> List[Dict[str, Any]]:
    """Reference peritext.ts:198-221: a patch opens at every written DURING
    slot whose effective marks change, and closes at the next written slot
    (or the end of the walk).  Shared by the interleaved-scan and sorted
    patch assemblers so the two paths cannot diverge on patch shaping."""
    written_idx = np.flatnonzero(written)
    if written_idx.size == 0:
        return []
    action = "addMark" if int(op_row[K.K_MACTION]) == 0 else "removeMark"
    mark_type = schema.ALL_MARKS[int(op_row[K.K_MTYPE])]
    attr_values = attrs.decode(int(op_row[K.K_MATTR]))

    patches: List[Dict[str, Any]] = []
    for j, p in enumerate(written_idx):
        if not (during[p] and changed[p]):
            continue
        start = int(vis[p])
        end = int(vis[written_idx[j + 1]]) if j + 1 < written_idx.size else obj_len
        # finishPartialPatch filters (peritext.ts:269-281).
        if end > start and start < obj_len:
            patch: Dict[str, Any] = {
                "action": action,
                "markType": mark_type,
                "path": ["text"],
                "startIndex": start,
                "endIndex": min(end, obj_len),
            }
            if action == "addMark" and mark_type in ("link", "comment"):
                patch["attrs"] = attr_values
            patches.append(patch)
    return patches


def _mark_span_patches(
    starts: np.ndarray,
    ends: np.ndarray,
    count: int,
    op_row: np.ndarray,
    attrs: AttrRegistry,
) -> List[Dict[str, Any]]:
    """Reference-format mark patches from one compact run-table row.

    The device compaction (kernels.compact_mark_records) already applied
    _mark_patch_list's walk — emission order, the next-written span ends,
    and the finishPartialPatch filters (a filtered lane reads
    ``end <= start`` and is skipped) — so host assembly is pure dict
    construction over the row's lanes."""
    if count <= 0:
        return []
    action = "addMark" if int(op_row[K.K_MACTION]) == 0 else "removeMark"
    mark_type = schema.ALL_MARKS[int(op_row[K.K_MTYPE])]
    attr_values = attrs.decode(int(op_row[K.K_MATTR]))
    patches: List[Dict[str, Any]] = []
    for j in range(min(count, starts.shape[0])):
        start = int(starts[j])
        end = int(ends[j])
        if end <= start:
            continue  # filtered lane (finishPartialPatch, applied on device)
        patch: Dict[str, Any] = {
            "action": action,
            "markType": mark_type,
            "path": ["text"],
            "startIndex": start,
            "endIndex": end,
        }
        if action == "addMark" and mark_type in ("link", "comment"):
            patch["attrs"] = attr_values
        patches.append(patch)
    return patches


def assemble_mark_patches(
    records: Dict[str, np.ndarray],
    r: int,
    i: int,
    op_row: np.ndarray,
    attrs: AttrRegistry,
) -> List[Dict[str, Any]]:
    return _mark_patch_list(
        records["written"][r, i],
        records["during"][r, i],
        records["changed"][r, i],
        records["vis"][r, i],
        int(records["obj_len"][r, i]),
        op_row,
        attrs,
    )


def assemble_patches_sorted(
    records: Dict[str, np.ndarray],
    r: int,
    text_rows: np.ndarray,
    text_pos: np.ndarray,
    char_buf: np.ndarray,
    mark_rows: np.ndarray,
    mark_pos: np.ndarray,
    table: Dict[str, Dict[str, Any]],
    attrs: AttrRegistry,
) -> List[Any]:
    """(pos, patch) pairs from the sorted merge's compact records.

    Text rows are FUSED (one record per run); a run expands to k insert
    patches at consecutive stream positions and visible indices with one
    shared inherited-marks decode — the per-char cost is dict construction,
    not mark resolution.  Byte-equal to the interleaved assembler's stream
    for the same delivery order (tests/test_sorted_merge differentials).
    """
    patches: List[Any] = []
    op_ids = list(table)
    mask_cache: Dict[bytes, Dict[str, Any]] = {}

    def decode_mask(row: np.ndarray) -> Dict[str, Any]:
        # Cheap frozen-structure copy (not deepcopy): each emitted patch
        # needs its own mutation-safe marks dict, but the values are plain
        # JSON shapes — deepcopy here ran once per inserted CHARACTER of a
        # fused run and dominated the single-ingest assembly breakdown.
        return _copy_jsonlike(_decode_mask_row(row, op_ids, table, mask_cache))

    kind = records["kind"][r]
    tvalid = records["tvalid"][r]
    index0 = records["index0"][r]
    for l in range(text_rows.shape[0]):
        kd = int(kind[l])
        if kd == K.KIND_PAD or not tvalid[l]:
            continue
        pos0 = int(text_pos[l])
        idx0 = int(index0[l])
        if kd == K.KIND_DELETE:
            patches.append(
                (pos0, {"path": ["text"], "action": "delete", "index": idx0, "count": 1})
            )
            continue
        if kd == K.KIND_INSERT_RUN:
            n = int(text_rows[l, K.K_RUN_LEN])
            start = int(text_rows[l, K.K_PAYLOAD])
            values = [chr(int(c)) for c in char_buf[start : start + n]]
        else:
            n = 1
            values = [chr(int(text_rows[l, K.K_PAYLOAD]))]
        row_mask = records["ins_mask"][r, l]
        for j in range(n):
            patches.append(
                (
                    pos0 + j,
                    {
                        "path": ["text"],
                        "action": "insert",
                        "index": idx0 + j,
                        "values": [values[j]],
                        "marks": decode_mask(row_mask),
                    },
                )
            )
    for m in range(mark_rows.shape[0]):
        if int(mark_rows[m, K.K_KIND]) != K.KIND_MARK:
            continue
        pos = int(mark_pos[m])
        for patch in _mark_patch_list(
            records["written"][r, m],
            records["during"][r, m],
            records["changed"][r, m],
            records["vis"][r, m],
            int(records["obj_len"][r, m]),
            mark_rows[m],
            attrs,
        ):
            patches.append((pos, patch))
    return patches


def assemble_patches_sorted_compact(
    records: Dict[str, np.ndarray],
    r: int,
    text_rows: np.ndarray,
    text_pos: np.ndarray,
    char_buf: np.ndarray,
    mark_rows: np.ndarray,
    mark_pos: np.ndarray,
    table: Dict[str, Dict[str, Any]],
    attrs: AttrRegistry,
) -> List[Any]:
    """assemble_patches_sorted over the compact run-table records,
    vectorized: run expansion, index/position arithmetic and char decoding
    run as numpy batch operations over all text rows at once, and mark
    patches come straight from the device-compacted spans — the per-patch
    Python work is dict construction only.  Emits the same (pos, patch)
    set as the planes assembler for the same launch; every stream position
    is unique per op (fusion is delivery-adjacency-gated), so the caller's
    stable sort-by-pos makes the merged streams byte-identical.
    """
    patches: List[Any] = []
    op_ids = list(table)
    mask_cache: Dict[bytes, Dict[str, Any]] = {}
    kind = np.asarray(text_rows[:, K.K_KIND])
    tvalid = np.asarray(records["tvalid"][r]).astype(bool)
    index0 = np.asarray(records["index0"][r])
    live = (kind != K.KIND_PAD) & tvalid

    for l in np.flatnonzero(live & (kind == K.KIND_DELETE)).tolist():
        patches.append(
            (
                int(text_pos[l]),
                {"path": ["text"], "action": "delete", "index": int(index0[l]), "count": 1},
            )
        )

    ins = np.flatnonzero(
        live & ((kind == K.KIND_INSERT) | (kind == K.KIND_INSERT_RUN))
    )
    if ins.size:
        is_run = kind[ins] == K.KIND_INSERT_RUN
        lens = np.where(is_run, text_rows[ins, K.K_RUN_LEN], 1).astype(np.int64)
        payload = text_rows[ins, K.K_PAYLOAD].astype(np.int64)
        total = int(lens.sum())
        row_of = np.repeat(np.arange(ins.size), lens)
        off = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
        buf_idx = np.minimum(payload[row_of] + off, char_buf.shape[0] - 1)
        codes = np.where(
            is_run[row_of], np.asarray(char_buf)[buf_idx], payload[row_of]
        )
        text = _codepoints_to_str(codes)
        pos_flat = (text_pos[ins][row_of] + off).tolist()
        idx_flat = (index0[ins][row_of] + off).tolist()
        row_marks = [
            _decode_mask_row(records["ins_mask"][r, l], op_ids, table, mask_cache)
            for l in ins.tolist()
        ]
        for j in range(total):
            patches.append(
                (
                    pos_flat[j],
                    {
                        "path": ["text"],
                        "action": "insert",
                        "index": idx_flat[j],
                        "values": [text[j]],
                        "marks": _copy_jsonlike(row_marks[row_of[j]]),
                    },
                )
            )

    mcount = np.asarray(records["mcount"][r])
    mk = np.flatnonzero(
        (np.asarray(mark_rows[:, K.K_KIND]) == K.KIND_MARK) & (mcount > 0)
    )
    for m in mk.tolist():
        pos = int(mark_pos[m])
        for patch in _mark_span_patches(
            records["mstart"][r, m],
            records["mend"][r, m],
            int(mcount[m]),
            mark_rows[m],
            attrs,
        ):
            patches.append((pos, patch))
    return patches


def fold_multi_groups(
    census: Dict[Tuple[int, int], set],
    *,
    types,
    attr_ids,
    ctrs,
    act_ids,
) -> None:
    """Fold mark-op columns into an allowMultiple group census:
    census[(type_id, attr_id)] accumulates distinct (ctr, act_id) op
    identities.  THE one definition of group identity — the live ingest
    census, the pre-launch overflow gate, and the checkpoint rebuild all
    fold through here, so they can never disagree.

    Each group's set is capped at PATCH_GROUP_K + 1 identities: the gate
    only asks "over cap?", so a set already past the cap never needs more
    members, and a long-lived universe's census stays O(groups * K) instead
    of growing with every allowMultiple op ever ingested.  The cap keeps
    the K+1 *smallest* identities, so the retained subset is a pure
    function of the identities seen — fold order (live ingest vs the
    checkpoint rebuild's per-replica table scan) cannot make two censuses
    disagree."""
    multi_by_id = schema.ALLOW_MULTIPLE_BY_ID
    cap = K.PATCH_GROUP_K + 1
    for t, attr, ctr, act in zip(types, attr_ids, ctrs, act_ids):
        t = int(t)
        if t < len(multi_by_id) and multi_by_id[t]:
            ops = census.setdefault((t, int(attr)), set())
            ops.add((int(ctr), int(act)))
            if len(ops) > cap:
                ops.discard(max(ops))


def fold_multi_group_rows(census: Dict[Tuple[int, int], set], rows) -> None:
    """fold_multi_groups over encoded op rows (mark rows only)."""
    rows = np.asarray(rows)
    if rows.size == 0:
        return
    marks = rows[rows[:, K.K_KIND] == K.KIND_MARK]
    fold_multi_groups(
        census,
        types=marks[:, K.K_MTYPE],
        attr_ids=marks[:, K.K_MATTR],
        ctrs=marks[:, K.K_CTR],
        act_ids=marks[:, K.K_ACT],
    )


class TpuUniverse:
    # Process-wide adaptive floor for the compact-readback span capacity:
    # an overflow in ANY universe raises it, so fresh universes (bench
    # legs rebuild one per run; fleets churn) start wide enough instead of
    # each re-paying the planes fallback once per lifetime.  Pow2, so the
    # jit cache stays bounded; an explicit PERITEXT_PATCH_SPAN_CAP pin
    # ignores the floor (tests own their cap).
    _span_cap_floor = 1

    def __init__(
        self,
        replica_ids: Sequence[str],
        capacity: int = 256,
        max_mark_ops: int = 64,
        max_actors: int = 64,
        device: Any = None,
    ) -> None:
        """``device`` pins the fleet's state and every launch's inputs to
        one device (a sharded serving plane gives each shard its own);
        None leaves them on JAX's default device."""
        self.replica_ids = list(replica_ids)
        self.index_of = {r: i for i, r in enumerate(self.replica_ids)}
        self.actors = ActorRegistry()
        self.attrs = AttrRegistry()
        self.max_actors = max_actors
        self.capacity = capacity
        self.max_mark_ops = max_mark_ops
        # Where host arrays go: per-replica tensors (leading R axis) and
        # replicated ones (rank tables).  Both are ``device`` until shard()
        # lays the fleet over a mesh.
        self._row_sharding: Any = device
        self._rep_sharding: Any = device
        self.states: DocState = make_empty_state(
            capacity, max_mark_ops, batch=(len(self.replica_ids),), device=device
        )
        # Host control-plane mirrors (never require device sync).
        self.clocks: List[Dict[str, int]] = [dict() for _ in self.replica_ids]
        self.lengths = [0] * len(self.replica_ids)
        self.mark_counts = [0] * len(self.replica_ids)
        # Host structural plane: per-replica object store (root map, nested
        # maps/lists — everything but the device text list) + the permanent
        # device binding (the first root makeList with key "text").
        # Replicas with equal ``store_versions`` hold equal stores and may
        # SHARE one ObjectStore instance (the converged-fleet fast path:
        # one deepcopy+apply per version class instead of per replica), so
        # stores must only ever be replaced via the _prepare copy-swap,
        # never mutated in place (TpuDoc's local path bumps its version).
        self.stores: List[ObjectStore] = [ObjectStore() for _ in self.replica_ids]
        self.store_versions: List[int] = [0] * len(self.replica_ids)
        self._store_version_counter = 0
        self.text_objs: List[Optional[str]] = [None] * len(self.replica_ids)
        # Distinct mark ops per allowMultiple resolution group ((type_id,
        # attr_id) -> {(ctr, act_id)}), unioned over every ingested change.
        # A conservative upper bound on any replica's per-group column
        # count, used to gate the cached patch scan (which resolves multi
        # groups over at most kernels.PATCH_GROUP_K columns) to the exact
        # interleaved fallback when a group grows past the cap.
        self._multi_groups: Dict[Tuple[int, int], set] = {}
        # Persisted per-slot per-type winner cache ([R, 2C, T, 4] device
        # array; derived state, never checkpointed): the patched sorted
        # merge maintains it across ingests, so its dominance init runs
        # once per universe lifetime in an all-patched workload.
        # Invalidated by anything that rewrites boundary rows without
        # maintaining it (non-patched merges, the interleaved fallback,
        # TpuDoc's local path, capacity growth, replica add/drop,
        # resharding).  The cache stores actor-RANK values, and interning
        # a new actor renumbers every rank (lexicographic order, ids.py),
        # so _wcaches_actors keys the cache to the registry size it was
        # built under.  (Mark-type registration needs no guard: the multi
        # array is padded to a fixed width, and a newly registered type
        # has no existing rows, so its cached entries are empty either
        # way.)
        self._wcaches = None
        self._wcaches_actors = 0
        # Per-mark-row span capacity of the compact patch readback
        # (kernels.compact_mark_records).  Adaptive: a batch whose true
        # span counts overflow it re-reads via the planes format (byte-
        # identical stream) and the cap grows to the observed maximum, so
        # a workload that keeps emitting wide mark patches stops paying
        # the fallback after its first overflow.
        if "PERITEXT_PATCH_SPAN_CAP" in os.environ:
            self._span_cap = _initial_span_cap()
        else:
            self._span_cap = max(_initial_span_cap(), TpuUniverse._span_cap_floor)
        # Causal mirror for the frontier-bounded window merge (ISSUE 12):
        # per-replica numpy copies of the committed element ids, tombstone
        # flags and boundary definedness, keyed to the states pytree OBJECT
        # the copy was read from — any path that assigns ``self.states``
        # without splicing the mirror (full-table merges, degrade, replica
        # elasticity, external restores) invalidates it automatically, and
        # the next window census lazily rebuilds it with one batched
        # readback.  Windowed commits splice the post-merge window planes
        # (read back with the records) instead, so the mirror is always a
        # pure readback of device truth — never a host-side replay.
        self._mirror: Optional[List[W.Mirror]] = None
        self._mirror_token: Any = None
        # Mirror equivalence classes: replicas with byte-equal mirrors
        # share a class id, so a converged fleet ingesting one shared
        # stream pays ONE census (and one mirror splice) per (class,
        # group) instead of per replica.  Classes are content hashes at
        # rebuild time and evolve deterministically on windowed commits
        # (equal class + equal gated batch => equal spliced mirror).
        self._mirror_class: List[Any] = []
        self._mirror_class_counter = 0
        # Census-rejection backoff: a streak of expensive census passes
        # that plan_windows rejected (wide hulls) means this workload is
        # paying a per-batch mirror rebuild (full-table commits invalidate
        # the mirror) for nothing — skip the census for a few batches
        # before probing again.
        self._window_reject_streak = 0
        self._window_census_skip = 0
        # Device-resident actor-rank cache (re-upload only when the actor
        # registry or its padded width changes — interning renumbers
        # ranks, and both events change the key).
        self._ranks_cache: Optional[List[Any]] = None
        # Keys of the programs the last apply_changes_with_patches
        # launched, and of those warm_patched ran ahead of traffic; once
        # warmed, patched launches keep to the warmed shapes (WARM_RUN).
        self.launch_keys: List[tuple] = []
        self.warmed_keys: set = set()
        self._warmed = False
        # Lightweight observability counters (the reference's observability
        # is console logging + the demo op panel, SURVEY §5; at batch scale
        # these are what perf debugging needs).
        self.stats: Dict[str, Any] = {
            "launches": 0,
            "ops_applied": 0,
            "rows_padded": 0,
            "capacity_growths": 0,
            "changes_ingested": 0,
            "duplicates_dropped": 0,
            "scan_fallbacks": 0,
            # Resilience counters: extra launch attempts taken (retry
            # policy) and batches that completed on the oracle CPU path
            # after the retry budget was exhausted.  "fastfails" counts
            # launch units an OPEN circuit breaker rejected without any
            # attempt (distinct from degraded_batches: a fast-failed
            # ingest ALSO degrades, but spends no retry/timeout budget).
            "launch_retries": 0,
            "degraded_batches": 0,
            "fastfails": 0,
        }

    # -- fleet elasticity ---------------------------------------------------

    def add_replicas(self, names: Sequence[str]) -> None:
        """Grow the fleet with fresh (empty) replicas.

        The elastic-recovery story (SURVEY §5): a new replica joins empty
        and catches up by ingesting ``ChangeLog.missing_changes(log.clock(),
        {})`` through the normal causal gate — exactly how the reference
        reconstructs any replica from the durable change log.
        """
        fresh = [n for n in names]
        for n in fresh:
            if n in self.index_of:
                raise ValueError(f"replica {n!r} already exists")
        if not fresh:
            return
        empty = make_empty_state(
            self.capacity, self.max_mark_ops, batch=(len(fresh),),
            device=self._rep_sharding,
        )
        self.states = jax.tree.map(
            lambda a, b: jax.numpy.concatenate([a, b]), self.states, empty
        )
        self._wcaches = None  # replica axis changed
        for n in fresh:
            self.index_of[n] = len(self.replica_ids)
            self.replica_ids.append(n)
            self.clocks.append({})
            self.lengths.append(0)
            self.mark_counts.append(0)
            self.stores.append(ObjectStore())
            # Version 0 always means "untouched empty store", so fresh
            # replicas may share a version class with untouched founders.
            self.store_versions.append(0)
            self.text_objs.append(None)

    def rename_replica(self, old: str, new: str) -> None:
        """Rebind an EMPTY replica row to a new id — pure host
        bookkeeping, zero device work.  The row must never have ingested
        anything (empty clock); the sharded serving plane uses this to
        hand a pow2-bucket pad row to a joining session without the
        drop+add double state rebuild."""
        if new in self.index_of:
            raise ValueError(f"replica {new!r} already exists")
        if old not in self.index_of:
            raise KeyError(f"unknown replica {old!r}")
        i = self.index_of[old]
        if self.clocks[i]:
            raise ValueError(
                f"cannot rename non-empty replica {old!r} (clock "
                f"{self.clocks[i]}); only untouched rows rebind"
            )
        del self.index_of[old]
        self.replica_ids[i] = new
        self.index_of[new] = i
        # Reset the host planes to the founder state (the row never saw
        # traffic, but a fresh store guards against aliasing a shared
        # version-0 instance under the new name's future mutations —
        # stores only ever swap via _prepare, which copies, so this is
        # belt-and-braces, not a repair).
        self.stores[i] = ObjectStore()
        self.store_versions[i] = 0
        self.text_objs[i] = None

    def drop_replicas(self, names: Sequence[str]) -> None:
        """Shrink the fleet (one gather; dropped replicas' state is gone —
        durable history lives in the change log, not the fleet)."""
        drop = set(names)
        missing = drop - set(self.replica_ids)
        if missing:
            raise KeyError(f"unknown replicas: {sorted(missing)}")
        keep = [i for i, n in enumerate(self.replica_ids) if n not in drop]
        if not keep:
            raise ValueError("cannot drop every replica")
        idx = jax.numpy.asarray(np.asarray(keep, np.int32))
        self.states = jax.tree.map(lambda x: x[idx], self.states)
        self._wcaches = None  # replica axis changed (a later add could
        # restore the old count with different row meanings)
        self.replica_ids = [self.replica_ids[i] for i in keep]
        self.index_of = {n: i for i, n in enumerate(self.replica_ids)}
        self.clocks = [self.clocks[i] for i in keep]
        self.lengths = [self.lengths[i] for i in keep]
        self.mark_counts = [self.mark_counts[i] for i in keep]
        self.stores = [self.stores[i] for i in keep]
        self.store_versions = [self.store_versions[i] for i in keep]
        self.text_objs = [self.text_objs[i] for i in keep]

    def shard(self, mesh, shard_seq: bool = True) -> None:
        """Lay the fleet's device state out over a (replica, seq) mesh.

        Ingestion keeps working unchanged — the jitted merge partitions
        over the mesh (GSPMD inserts the collectives), and every readback
        path (spans/texts/digests/cursors) gathers transparently.  Call
        after construction or any elasticity change; replica count must
        divide the mesh's replica axis.
        """
        from peritext_tpu.parallel import shard_states

        self.states = shard_states(self.states, mesh, shard_seq=shard_seq)
        self._row_sharding = NamedSharding(mesh, PartitionSpec("replica"))
        self._rep_sharding = NamedSharding(mesh, PartitionSpec())
        self._ranks_cache = None  # re-upload under the new placement
        self._wcaches = None  # placement changed; rebuilt on next patched merge

    def _put(self, tree: Any, replicated: bool = False) -> Any:
        """Host -> the device(s) holding this universe's state.  Per-replica
        tensors split like the state's replica axis; ``replicated`` ones
        (rank and flag tables) go whole to every device, as do per-replica
        tensors whose width the mesh's replica axis no longer divides
        (after add/drop_replicas)."""
        sharding = self._row_sharding
        if replicated or (
            isinstance(sharding, NamedSharding)
            and len(self.replica_ids) % sharding.mesh.shape["replica"]
        ):
            sharding = self._rep_sharding
        return jax.device_put(tree, sharding)

    def _put_states(self, host: DocState) -> DocState:
        """Host-built state arrays -> exactly where the current state's
        fields live (committed fields keep their device or mesh
        sharding; uncommitted ones stay on the default device)."""
        return jax.tree.map(
            lambda h, old: jax.device_put(h, old.sharding if old.committed else None),
            host,
            self.states,
        )

    # -- capacity management ------------------------------------------------

    def _ensure_capacity(self, need_len: int, need_marks: int) -> None:
        new_c, new_m = self.capacity, self.max_mark_ops
        while need_len > new_c:
            new_c *= 2
        while need_marks > new_m:
            new_m *= 2
        if (new_c, new_m) != (self.capacity, self.max_mark_ops):
            self.stats["capacity_growths"] += 1
            states = [
                grow_state(index_state(self.states, i), new_c, new_m)
                for i in range(len(self.replica_ids))
            ]
            self.states = stack_states(states)
            self.capacity, self.max_mark_ops = new_c, new_m
            self._wcaches = None  # slot coordinates changed shape

    def _ranks(self) -> np.ndarray:
        ranks = self.actors.ranks()
        n = self.max_actors
        while len(ranks) > n:
            n *= 2
        self.max_actors = n
        out = np.zeros(n, np.int32)
        out[: len(ranks)] = ranks
        return out

    def _ranks_host(self) -> np.ndarray:
        """Cached padded host rank table, rebuilt only when the actor
        registry changes.  The key is checked BEFORE building the table:
        interning an actor changes len(actors) (and possibly the padded
        width), so a hit guarantees the cached table is current — the
        window census and the device upload of one ingest share one build.
        Callers treat the returned array as read-only."""
        key = (len(self.actors.actors), self.max_actors)
        c = self._ranks_cache
        if c is not None and c[0] == key:
            return c[1]
        ranks = self._ranks()  # may grow max_actors: re-key below
        self._ranks_cache = [
            (len(self.actors.actors), self.max_actors), ranks, None
        ]
        return ranks

    def _ranks_jax(self):
        """Device-resident rank table (one upload per registry change, not
        per launch — transfer overhead dominates small windowed launches)."""
        host = self._ranks_host()
        c = self._ranks_cache
        if c[2] is None:
            c[2] = self._put(host, replicated=True)
        return c[2]

    # -- resilient launch policy -------------------------------------------

    def _run_launch(self, attempt, needs_barrier: bool = False):
        """Run a device-launch attempt under the retry/backoff policy.

        ``attempt()`` fires the ``device_launch`` site itself, runs the
        kernel(s) against the *committed* (immutable) state pytree and
        returns ``(result, barrier_leaf)`` — nothing it does mutates
        ``self``, so a failed attempt needs no rollback: its result is
        simply discarded and the next attempt reruns from the same inputs.

        With ``needs_barrier`` (strict commit) or a configured
        ``PERITEXT_LAUNCH_TIMEOUT``, each attempt blocks on a host readback
        of ``barrier_leaf`` — a completion signal that also proves the
        result reached the host — and a late readback counts as a failed
        attempt.  After the budget
        is exhausted, raises :class:`DeviceLaunchError` carrying the last
        cause; callers then either degrade to the oracle CPU path or
        propagate with the committed state untouched.

        Health-plane gating (runtime/health.py): with an active
        ``device_launch`` breaker, an OPEN circuit fast-fails here —
        DeviceLaunchError with a :class:`health.BreakerOpenError` cause,
        zero attempts, zero budget spend — so a wedged backend charges
        each batch only the degrade path's cost.  Half-open admits exactly
        one canary launch (``retries`` forced to 0); its success closes
        the circuit, its failure re-opens with a fresh cool-down.  A trip
        mid-budget stops the remaining retries (they would fast-fail
        anyway).
        """
        br = health.breaker("device_launch")
        decision = health.ALLOW if br is None else br.admit()
        if decision == health.FASTFAIL:
            self.stats["fastfails"] = self.stats.get("fastfails", 0) + 1
            if telemetry.enabled:
                telemetry.flow_keep()  # breaker-rejected: tail-interesting
                telemetry.record(
                    "ingest.launch",
                    flow=telemetry.current_flow(),
                    outcome="fastfail",
                )
            raise DeviceLaunchError(0, health.BreakerOpenError("device_launch"))
        retries, backoff, timeout = _launch_policy()
        if decision == health.CANARY:
            retries = 0  # half-open admits exactly ONE probe launch
        last: Optional[BaseException] = None
        attempts = 0
        try:
            for i in range(retries + 1):
                if i:
                    self.stats["launch_retries"] += 1
                    sleep_s = min(backoff * (2 ** (i - 1)), 2.0)
                    if telemetry.enabled:
                        telemetry.counter("ingest.launch_retries")
                        telemetry.observe("ingest.backoff_seconds", sleep_s)
                    time.sleep(sleep_s)
                t0 = time.monotonic()
                attempts = i + 1
                try:
                    if telemetry.enabled:
                        telemetry.counter("ingest.launch_attempts")
                    with telemetry.span("ingest.launch_attempt", attempt=i):
                        if telemetry.enabled:
                            # Join whatever causal lanes the enclosing
                            # flush/change/delivery scoped onto this
                            # thread — every retry attempt is its own
                            # flow step, so Perfetto lanes show the
                            # retries, not just the final success.
                            telemetry.flow_steps(attempt=i)
                        result, barrier_leaf = attempt()
                        if needs_barrier or timeout > 0:
                            faults.fire("device_readback")
                            tb = time.monotonic()
                            np.asarray(barrier_leaf)
                            if telemetry.enabled:
                                telemetry.observe(
                                    "ingest.readback_wait_seconds",
                                    time.monotonic() - tb,
                                )
                            if timeout > 0 and time.monotonic() - t0 > timeout:
                                raise TimeoutError(
                                    f"device launch attempt exceeded the {timeout}s deadline"
                                )
                except Exception as exc:
                    if not _retryable(exc):
                        raise  # semantic error: no backend-health signal
                    if telemetry.enabled:
                        telemetry.counter("ingest.launch_failures")
                        # A failed attempt makes every lane riding this
                        # launch tail-interesting (retention guarantee for
                        # sampled traces), whether or not a retry saves it.
                        telemetry.flow_keep()
                        telemetry.record(
                            "ingest.launch",
                            flow=telemetry.current_flow(),
                            outcome="fail",
                            attempt=i,
                            error=type(exc).__name__,
                        )
                    if br is not None:
                        br.record_failure()
                    last = exc
                    if br is not None and br.state == health.OPEN:
                        break  # tripped mid-budget: stop burning retries
                    continue
                if br is not None:
                    br.record_success()
                if telemetry.enabled:
                    telemetry.record(
                        "ingest.launch",
                        flow=telemetry.current_flow(),
                        outcome="ok",
                        attempt=i,
                    )
                return result
            # Launch budget exhausted: the hung-device post-mortem moment —
            # dump the flight recorder + registry before the caller degrades
            # (or propagates), while the failing batch's trail is still in
            # the ring.
            telemetry.blackbox_dump(
                "launch_budget_exhausted",
                site="device_launch",
                attempts=attempts,
                cause=repr(last),
            )
            raise DeviceLaunchError(attempts, last) from last
        except BaseException:
            # Any verdict-less exit — a semantic error, or a BaseException
            # (KeyboardInterrupt mid-dispatch) the retry loop never
            # classifies — must release a held canary slot, or the breaker
            # would fast-fail forever with no probe able to run.  abandon()
            # is a no-op when no canary is in flight (record_success /
            # record_failure already cleared it on classified outcomes).
            if br is not None:
                br.abandon()
            raise

    # -- the causal gate (host) --------------------------------------------

    def _gate(self, clock: Dict[str, int], changes: Sequence[Change]) -> List[Change]:
        """Order + validate a change batch against a replica clock.

        Single-pass equivalent of the reference's applyChange seq/deps gate
        (micromerge.ts:501-509) + the retry loop (test/merge.ts:4-23).
        Delivery order is preserved among causally-ready changes
        (causal_order), because patch streams are order-sensitive and must
        match what an incremental replica consuming the same delivery order
        would emit.  Duplicate (already-seen) changes drop idempotently.

        ``clock`` is mutated in place; callers pass a *copy* of the replica
        clock and commit it back only after the device launch succeeds, so a
        gate failure on one replica (or a failed launch) can never leave
        another replica's clock claiming changes its state never received.
        """
        seen = set()
        fresh = []
        dupes = 0
        for c in changes:
            key = (c["actor"], c["seq"])
            if c["seq"] > clock.get(c["actor"], 0) and key not in seen:
                seen.add(key)
                fresh.append(c)
            else:
                dupes += 1
        ordered = causal_order(fresh, clock)
        for change in ordered:
            clock[change["actor"]] = change["seq"]
        return ordered, dupes

    def _prepare(self, batches: List[Sequence[Change]]) -> Dict[str, Any]:
        """Spanned wrapper for :meth:`_prepare_impl`: the first of the
        host phases before a launch (``ingest.prepare``; then
        ``ingest.stage``, ``ingest.window_census`` and ``ingest.put``,
        siblings on the profiler's host plane)."""
        with telemetry.span("ingest.prepare"):
            return self._prepare_impl(batches)

    def _prepare_impl(self, batches: List[Sequence[Change]]) -> Dict[str, Any]:
        """Gate + encode every replica without touching committed state.

        Raises before any commit if any replica's batch is causally
        unsatisfiable; on success returns everything the launch and the
        post-launch commit need.

        Fleet-scale shape: thousands of replicas commonly ingest the *same*
        change stream from the same clock (fleet_demo, the bench, catch-up
        sync).  Gate + encode are therefore memoized per distinct
        (batch identity, clock state, text object) group — the per-replica
        output is a group index, and the expensive Python/string work runs
        once per group instead of once per replica.
        """
        n = len(batches)
        groups: List[Dict[str, Any]] = []
        memo: Dict[Any, int] = {}
        group_of = np.zeros(n, np.int32)
        n_ingested = 0
        # Group identity is change *content* (canonical-JSON digest), not
        # object identity, so per-replica deserialized copies of the same
        # stream (catch-up sync) still share one gate/encode pass.  The
        # digest is cached by object id for the duration of this call, so
        # the common shared-list fleet case hashes each change once total.
        hash_by_id: Dict[int, str] = {}

        def change_digest(c: Change) -> str:
            h = hash_by_id.get(id(c))
            if h is None:
                h = hashlib.sha1(
                    json.dumps(c, sort_keys=True, separators=(",", ":")).encode()
                ).hexdigest()
                hash_by_id[id(c)] = h
            return h

        for r, changes in enumerate(batches):
            clock = self.clocks[r]
            text_obj = self.text_objs[r]
            key = (
                tuple(change_digest(c) for c in changes),
                tuple(sorted(clock.items())),
                text_obj,
            )
            gi = memo.get(key)
            if gi is None:
                new_clock = dict(clock) if changes else clock
                ordered, dupes = self._gate(new_clock, changes)
                rows, host_ops, counts = encode_changes(
                    ordered, self.actors, self.attrs, text_obj=text_obj
                )
                gi = len(groups)
                memo[key] = gi
                groups.append(
                    {
                        "clock": new_clock,
                        "ordered": ordered,
                        "dupes": dupes,
                        "rows": rows,
                        "host_ops": host_ops,
                        "row_pos": counts["row_pos"],
                        "text_obj": counts["text_obj"],
                        "inserts": counts["insert"],
                        "marks": counts["mark"],
                    }
                )
            n_ingested += len(groups[gi]["ordered"])
            group_of[r] = gi

        # Host structural ops dry-run against store *copies* (the oracle's
        # per-object dispatch; host objects are tiny by design — the text
        # data plane is on device).  A bad op (unknown object, dangling
        # element) raises here, before anything commits; _commit later swaps
        # the copies in, preserving the all-or-nothing contract.
        # One deepcopy+apply runs per (group, store-version) class, not per
        # replica: a converged fleet ingesting a shared stream (the common
        # case — genesis at R=100k) pays for ONE application however many
        # replicas share it; the resulting store instance is shared and a
        # fresh version allocated per class keeps the equality invariant.
        new_stores: Dict[int, ObjectStore] = {}
        new_versions: Dict[int, int] = {}
        host_patches: Dict[int, List[Any]] = {}
        by_class: Dict[Any, Any] = {}
        for r in range(n):
            g = groups[group_of[r]]
            if not g["host_ops"]:
                continue
            key = (group_of[r], self.store_versions[r])
            hit = by_class.get(key)
            if hit is None:
                store = copy.deepcopy(self.stores[r])
                emitted: List[Any] = []
                for pos, op in g["host_ops"]:
                    emitted.extend((pos, p) for p in apply_host_op(store, op))
                if g["text_obj"] is not None:
                    store.device_objects.add(g["text_obj"])
                self._store_version_counter += 1
                hit = by_class[key] = (store, self._store_version_counter, emitted)
            new_stores[r], new_versions[r], host_patches[r] = hit

        ins = np.asarray([g["inserts"] for g in groups], np.int64)[group_of]
        mks = np.asarray([g["marks"] for g in groups], np.int64)[group_of]
        lengths = np.asarray(self.lengths, np.int64) + ins
        mark_counts = np.asarray(self.mark_counts, np.int64) + mks
        return {
            "groups": groups,
            "group_of": group_of,
            "new_stores": new_stores,
            "new_store_versions": new_versions,
            "host_patches": host_patches,
            "new_lengths": lengths,
            "new_mark_counts": mark_counts,
            "ingested": n_ingested,
            "need_len": int(lengths.max(initial=0)),
            "need_marks": int(mark_counts.max(initial=0)),
        }

    def _account_rows(self, groups, group_of):
        """Per-group replica counts + row counts; tallies ops_applied."""
        sizes = np.bincount(group_of, minlength=len(groups))
        row_counts = np.asarray([g["rows"].shape[0] for g in groups], np.int64)
        self.stats["ops_applied"] += int((row_counts * sizes).sum())
        return sizes, row_counts

    def _commit(self, prep: Dict[str, Any]) -> None:
        """Publish a prepared batch's control-plane effects (post-launch)."""
        groups = prep["groups"]
        group_of = prep["group_of"]
        self.lengths = [int(v) for v in prep["new_lengths"]]
        self.mark_counts = [int(v) for v in prep["new_mark_counts"]]
        for r in range(len(self.replica_ids)):
            g = groups[group_of[r]]
            if g["ordered"]:
                # Each replica owns its clock dict (sharing one dict across a
                # group would alias later per-replica clock mutations).
                self.clocks[r] = dict(g["clock"])
            if r in prep["new_stores"]:
                # Host structural ops were pre-applied to a store copy in
                # _prepare; publishing is a pointer swap (shared across the
                # replica's version class).
                self.stores[r] = prep["new_stores"][r]
                self.store_versions[r] = prep["new_store_versions"][r]
                if g["text_obj"] is not None:
                    self.text_objs[r] = g["text_obj"]
        self.stats["changes_ingested"] += prep["ingested"]
        sizes = np.bincount(group_of, minlength=len(groups))
        dupes = np.asarray([g["dupes"] for g in groups], np.int64)
        self.stats["duplicates_dropped"] += int((dupes * sizes).sum())
        for g in groups:
            self._count_multi_groups(g["rows"])

    def _count_multi_groups(self, rows: np.ndarray) -> None:
        """Fold a batch's allowMultiple mark rows into _multi_groups."""
        fold_multi_group_rows(self._multi_groups, rows)

    def _multi_group_need(self, extra_rows: List[np.ndarray]) -> int:
        """Largest allowMultiple resolution group any of this batch's multi
        ops targets, once ``extra_rows`` land (conservative: unioned over
        all replicas; 0 when the batch carries no multi ops).  Only groups
        the batch actually resolves matter: the cached scan compacts
        columns per *batch* multi op, so untargeted groups can grow past
        the cap without affecting correctness.  Sizes saturate at
        PATCH_GROUP_K + 1 (the census cap), which is all the overflow gate
        and the delta scan's group_k bucketing need."""
        pending: Dict[Tuple[int, int], set] = {}
        for rows in extra_rows:
            fold_multi_group_rows(pending, rows)
        return max(
            (
                len(ops | self._multi_groups.get(key, set()))
                for key, ops in pending.items()
            ),
            default=0,
        )

    # -- frontier-bounded window merge: host census + causal mirror ----------

    def _mirrors(self) -> List[W.Mirror]:
        """Per-replica causal mirrors, rebuilt lazily (one batched D2H
        readback of committed state) whenever any non-windowed path
        reassigned ``self.states`` since the last windowed commit."""
        if self._mirror_token is self.states and self._mirror is not None:
            return self._mirror
        ec = np.asarray(self.states.elem_ctr)
        ea = np.asarray(self.states.elem_act)
        dl = np.asarray(self.states.deleted)
        bd = np.asarray(self.states.bnd_def)
        # Byte-equal replicas share ONE Mirror instance (keyed by the same
        # content hash that forms their census class): a converged fleet
        # rebuild copies O(classes * n), not O(R * n).  Safe to share
        # because mirrors are treated as immutable everywhere — splices
        # replace them, never mutate in place.
        mirrors: List[W.Mirror] = []
        classes: List[Any] = []
        shared: Dict[str, W.Mirror] = {}
        for r, n in enumerate(self.lengths):
            digest = hashlib.sha1(
                b"".join((
                    ec[r, :n].tobytes(),
                    ea[r, :n].tobytes(),
                    dl[r, :n].tobytes(),
                    bd[r, : 2 * n].tobytes(),
                ))
            ).hexdigest()
            m = shared.get(digest)
            if m is None:
                m = W.make_mirror(
                    ec[r, :n].copy(), ea[r, :n].copy(), dl[r, :n].copy(),
                    bd[r, : 2 * n].copy(),
                )
                shared[digest] = m
            mirrors.append(m)
            classes.append(digest)
        self._mirror = mirrors
        self._mirror_class = classes
        self._mirror_token = self.states
        self.stats["window_rebuilds"] = self.stats.get("window_rebuilds", 0) + 1
        if telemetry.enabled:
            telemetry.counter("ingest.window_rebuilds")
            telemetry.counter(
                "ingest.d2h_bytes",
                int(ec.nbytes + ea.nbytes + dl.nbytes + bd.nbytes),
            )
        return self._mirror

    def _reach_bound(
        self, rows: List[np.ndarray], inserts: Sequence[int], group_of: np.ndarray
    ) -> int:
        """The device reach bound (kernels.window_reach_bound) of a batch
        over the committed state: per group, its op rows and insert count;
        ``group_of`` maps each replica to its group."""
        ids, head = W.reach_queries(rows)
        host = (ids[group_of], head[group_of], np.asarray(inserts, np.int32)[group_of])
        if telemetry.enabled:
            telemetry.counter("ingest.h2d_bytes", int(sum(a.nbytes for a in host)))
        s = self.states
        return int(K.window_reach_bound(s.elem_ctr, s.elem_act, s.length, *self._put(host)))

    def _reach_rejects(self, prep: Dict[str, Any], min_width: int) -> bool:
        """Whether the device reach bound alone shows the census would
        reject this batch (every window too wide), so the stale mirror
        need not be rebuilt to find that out.  A necessary condition only:
        False leaves the decision to the census."""
        groups = prep["groups"]
        bound = self._reach_bound(
            [g["rows"] for g in groups], [g["inserts"] for g in groups], prep["group_of"]
        )
        self.stats["window_reach_checks"] = self.stats.get("window_reach_checks", 0) + 1
        if telemetry.enabled:
            telemetry.counter("ingest.window_reach_checks")
        if not W.reach_rejects(bound, self.capacity, min_width):
            return False
        self.stats["window_reach_rejects"] = self.stats.get("window_reach_rejects", 0) + 1
        if telemetry.enabled:
            telemetry.counter("ingest.window_reach_rejects")
        return True

    def _window_plan(self, prep: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """Window plan for a prepared batch, or None for the full path.

        Gating: PERITEXT_MERGE_WINDOW=0 pins full; chunked launches
        (PERITEXT_SORTED_CHUNK / PERITEXT_PATCH_CHUNK) slice the replica
        axis and stay full-table; small documents
        (< PERITEXT_MERGE_WINDOW_MIN) aren't worth the gather/scatter; and
        plan_windows itself falls back when any replica's census cannot
        bound its batch or the bucketed window would cover more than half
        the table.  Where the mirror is stale, the device reach bound
        (:meth:`_reach_rejects`) runs first and rejects, without the
        rebuild, a batch whose window cannot fit."""
        if not _window_enabled():
            return None
        if os.environ.get("PERITEXT_SORTED_CHUNK") or os.environ.get(
            "PERITEXT_PATCH_CHUNK"
        ):
            return None
        if self.capacity < _window_min_cap():
            return None
        groups, group_of = prep["groups"], prep["group_of"]
        n = len(self.replica_ids)
        rows_of = [groups[group_of[r]]["rows"] for r in range(n)]
        ins_of = [int(groups[group_of[r]]["inserts"]) for r in range(n)]
        # Genesis fast-reject BEFORE the mirror readback: a replica whose
        # batch carries rows while its document is empty always falls back
        # (replica_window returns None on n == 0), so don't pay a
        # fleet-wide D2H rebuild to find that out.
        if any(
            self.lengths[r] == 0 and rows_of[r].shape[0] for r in range(n)
        ):
            return None
        # Backoff after a rejection streak: a census below this point can
        # cost a mirror rebuild (full-table commits invalidated it), so a
        # workload whose hulls are persistently too wide would otherwise
        # pay a fleet-wide D2H per batch the reach bound cannot rule out.
        if self._window_census_skip > 0:
            self._window_census_skip -= 1
            self.stats["window_census_skips"] = (
                self.stats.get("window_census_skips", 0) + 1
            )
            if telemetry.enabled:
                telemetry.counter("ingest.window_census_skips")
            return None
        ranks = self._ranks_host()
        min_width = _warm_narrowest(self.capacity) if self._warmed else W.MIN_WINDOW
        with telemetry.span("ingest.window_census"):
            # A stale mirror costs a fleet-wide readback to rebuild: first
            # ask the device whether the census could accept at all.
            if self._mirror_token is not self.states and self._reach_rejects(
                prep, min_width
            ):
                plan = None
            else:
                mirrors = self._mirrors()
                keys = [
                    (self._mirror_class[r], int(group_of[r])) for r in range(n)
                ]
                plan = W.plan_windows(
                    mirrors, rows_of, ins_of, ranks, self.capacity,
                    _window_min_cap(), census_keys=keys, min_width=min_width,
                )
        if plan is None:
            self._window_reject_streak += 1
            threshold = _window_backoff()
            if threshold and self._window_reject_streak >= threshold:
                self._window_census_skip = 2 * threshold
                self._window_reject_streak = 0
        else:
            self._window_reject_streak = 0
            if telemetry.enabled:
                telemetry.counter("ingest.window_planned")
                telemetry.observe(
                    "ingest.window_frac",
                    int(round(100 * plan["w_cap"] / self.capacity)),
                )
        return plan

    def _mirror_commit(
        self, wplan: Dict[str, Any], wrec: Dict[str, np.ndarray], prep: Dict[str, Any]
    ) -> None:
        """Splice a windowed launch's post-merge window readback into the
        mirrors and re-key them to the just-committed states pytree.  Runs
        after ``self.states`` is assigned (the token must key the NEW
        pytree); only the group insert counts are read from ``prep``, so
        ordering against ``_commit`` doesn't matter."""
        groups, group_of = prep["groups"], prep["group_of"]
        starts, hulls = wplan["starts"], wplan["hulls"]
        mirrors = self._mirror
        assert mirrors is not None
        # Splice + class evolution deduped per (mirror class, group):
        # byte-equal mirrors ingesting the same gated batch produce
        # byte-equal spliced mirrors, so class members SHARE the spliced
        # arrays (mirrors are replaced, never mutated in place) and the
        # new class id.
        shared: Dict[Any, Tuple[W.Mirror, int]] = {}
        for r in range(len(self.replica_ids)):
            hull = int(hulls[r])
            ins = int(groups[group_of[r]]["inserts"])
            if hull == 0 and ins == 0:
                continue
            key = (self._mirror_class[r], int(group_of[r]))
            hit = shared.get(key)
            if hit is None:
                self._mirror_class_counter += 1
                hit = (
                    W.splice_mirror(
                        mirrors[r],
                        int(starts[r]),
                        hull,
                        hull + ins,
                        wrec["w_ctr"][r],
                        wrec["w_act"][r],
                        wrec["w_del"][r],
                        wrec["w_def"][r],
                    ),
                    self._mirror_class_counter,
                )
                shared[key] = hit
            mirrors[r], self._mirror_class[r] = hit
        self._mirror_token = self.states

    def _assert_states_match(self, ref, got, wplan, prep) -> None:
        """PERITEXT_WINDOW_CHECK helper: compare a windowed result against
        the full-table recompute of the same batch, field by field."""
        import dataclasses as _dc

        for f in _dc.fields(ref):
            a = np.asarray(getattr(ref, f.name))
            b = np.asarray(getattr(got, f.name))
            if not (a == b).all():
                bad = np.argwhere(a != b)
                groups, group_of = prep["groups"], prep["group_of"]
                rows = {
                    r: groups[group_of[r]]["rows"].tolist()
                    for r in set(int(x[0]) for x in bad[:8])
                }
                raise RuntimeError(
                    "windowed merge diverged from full-table on plane "
                    f"{f.name}: first diffs {bad[:8].tolist()}; wplan starts="
                    f"{wplan['starts'].tolist()} hulls={wplan['hulls'].tolist()} "
                    f"w_cap={wplan['w_cap']}; rows={rows}"
                )

    def _window_fallback(self, launches: int = 1, d2h_bytes: int = 0) -> None:
        """Tally a windowed launch the device census check rejected (stale
        mirror / census bug): the caller discards the result and relaunches
        the full-table path — correctness never depends on the census.  The
        rejected launch still ran to completion on device, so it stays in
        the launch/traffic accounting (its window readback is real
        D2H traffic; the op-tensor H2D upload is shared with the relaunch
        and tallied once, at the relaunch's commit)."""
        self.stats["window_fallbacks"] = self.stats.get("window_fallbacks", 0) + 1
        self.stats["launches"] += launches
        _log.warning(
            "windowed merge census check failed on device; relaunching the "
            "full-table path"
        )
        if telemetry.enabled:
            telemetry.counter("ingest.window_fallbacks")
            telemetry.counter("ingest.launches", launches)
            if d2h_bytes:
                telemetry.counter("ingest.d2h_bytes", d2h_bytes)

    # -- oracle degradation (the CPU fallback after retry exhaustion) --------

    def _degrade_apply(self, prep: Dict[str, Any]) -> Dict[int, List[Any]]:
        """Traced wrapper for :meth:`_degrade_apply_impl`: the degradation
        is a seam every affected causal lane must step through (it IS the
        batch's completion path), and a flight-recorder event marks it."""
        with telemetry.span("ingest.degrade", ingested=prep["ingested"]):
            if telemetry.enabled:
                # A degraded batch is exactly the lane a tail-sampled
                # production trace must never drop: mark it explicitly so
                # retention does not hinge on arg-sniffing the seam.
                telemetry.flow_keep()
                telemetry.flow_steps(path="degrade")
                telemetry.record(
                    "ingest.degrade", outcome="ok", ingested=prep["ingested"]
                )
            return self._degrade_apply_impl(prep)

    def _degrade_apply_impl(self, prep: Dict[str, Any]) -> Dict[int, List[Any]]:
        """Complete a prepared batch through the oracle CPU engine.

        The resilience endgame: the device launch kept failing past its
        retry budget, so the batch re-applies per replica through the host
        :class:`ObjectStore` (the oracle's per-object dispatch — reference
        micromerge.ts:534-608) and the result is written back into the
        dense device arrays.  To callers a degraded ingest is
        indistinguishable from a successful launch — same patches, same
        clocks/lengths/roots, same device-plane state — just O(ops x
        length) scalar Python instead of one kernel launch.

        Steps, per replica with a non-empty gated batch:

        1. Read back the *committed* pre-batch device plane (committed
           data — this readback does not depend on the failed launch) and
           materialize it into oracle list metadata inside a copy of the
           replica's host store: elements -> :class:`ListItem` rows,
           boundary bitsets -> op-id sets, the mark table -> ``mark_ops``
           entries.
        2. Apply every gated wire op sequentially via ``store.apply_op`` —
           the literal oracle engine, so patches and final state carry
           reference semantics by construction.
        3. Convert the list back into dense arrays (new mark ops append to
           the table in batch order, exactly as the kernel would) and
           restore the store's device placeholder, so the staged store
           equals what the non-degraded host-op path would have produced.

        Nothing mutates ``self`` until every replica converts cleanly; a
        mid-degrade failure therefore leaves the committed state untouched
        (the same all-or-nothing contract as a launch).  Returns the
        ``(pos, patch)`` stream per replica index and commits the batch.
        """
        groups, group_of = prep["groups"], prep["group_of"]
        self.stats["degraded_batches"] += 1
        if telemetry.enabled:
            telemetry.counter("ingest.degraded_batches")
            telemetry.counter("ingest.path.degraded")
        _log.warning(
            "device launch retry budget exhausted; ingesting %d change(s) "
            "via the oracle CPU degradation path",
            prep["ingested"],
        )
        # One committed-state readback for the whole fleet (np.array:
        # writable host copies — these become the new device arrays).
        elem_ctr = np.array(self.states.elem_ctr)
        elem_act = np.array(self.states.elem_act)
        deleted = np.array(self.states.deleted)
        chars = np.array(self.states.chars)
        bnd_def = np.array(self.states.bnd_def)
        bnd_mask = np.array(self.states.bnd_mask)
        mark_cols = {
            f: np.array(getattr(self.states, "mark_" + f))
            for f in ("ctr", "act", "action", "type", "attr")
        }
        length_col = np.array(self.states.length)
        mark_count_col = np.array(self.states.mark_count)
        words = bnd_mask.shape[-1]

        out: Dict[int, List[Any]] = {}
        staged: List[Tuple[int, ObjectStore]] = []
        for r in range(len(self.replica_ids)):
            g = groups[group_of[r]]
            if not g["ordered"]:
                out[r] = []
                continue
            store = copy.deepcopy(self.stores[r])
            text_obj = g["text_obj"] if g["text_obj"] is not None else self.text_objs[r]
            n_el = self.lengths[r]
            n_mk = self.mark_counts[r]

            # Ids of the existing mark table rows (bit m <=> table row m).
            old_mark_ids = [
                make_op_id(int(mark_cols["ctr"][r, m]), self.actors.actor(int(mark_cols["act"][r, m])))
                for m in range(n_mk)
            ]
            char_of: Dict[str, int] = {}
            injected_ids: List[str] = []
            text_mark_new: List[str] = []

            bound = text_obj is not None and isinstance(
                store.metadata.get(text_obj), list
            )
            if bound:
                # 1. Materialize the device plane into the store copy.
                store.device_objects.discard(text_obj)
                values = store.objects[text_obj]
                values.clear()  # in place: the parent map aliases this list
                meta: List[ListItem] = []
                for i in range(n_el):
                    eid = make_op_id(
                        int(elem_ctr[r, i]), self.actors.actor(int(elem_act[r, i]))
                    )
                    item = ListItem(eid, eid, bool(deleted[r, i]))
                    for side, p in (("before", 2 * i), ("after", 2 * i + 1)):
                        if bnd_def[r, p]:
                            row = bnd_mask[r, p]
                            item.set_side(
                                side,
                                {
                                    old_mark_ids[m]
                                    for m in range(n_mk)
                                    if row[m // 32] >> (m % 32) & 1
                                },
                            )
                    meta.append(item)
                    char_of[eid] = int(chars[r, i])
                    if not item.deleted:
                        values.append(chr(int(chars[r, i])))
                store.metadata[text_obj] = meta
                for m, op_id in enumerate(old_mark_ids):
                    if op_id not in store.mark_ops:
                        op: Dict[str, Any] = {
                            "opId": op_id,
                            "action": "addMark"
                            if int(mark_cols["action"][r, m]) == 0
                            else "removeMark",
                            "markType": schema.ALL_MARKS[int(mark_cols["type"][r, m])],
                        }
                        attrs = self.attrs.decode(int(mark_cols["attr"][r, m]))
                        if attrs is not None:
                            op["attrs"] = attrs
                        store.mark_ops[op_id] = op
                        injected_ids.append(op_id)

            # 2. Sequential oracle application of the whole gated batch.
            pairs: List[Any] = []
            pos = 0
            for change in g["ordered"]:
                for op in change["ops"]:
                    pairs.extend((pos, p) for p in apply_host_op(store, op))
                    if op.get("obj") == text_obj and op["action"] in (
                        "addMark",
                        "removeMark",
                    ):
                        text_mark_new.append(op["opId"])
                    pos += 1

            # 3. Convert the (possibly batch-created) text list back into
            # dense device arrays and restore the placeholder.
            if text_obj is not None and isinstance(store.metadata.get(text_obj), list):
                final_meta: List[ListItem] = store.metadata[text_obj]
                rows = g["rows"]
                for row in rows:
                    op_id = make_op_id(
                        int(row[K.K_CTR]), self.actors.actor(int(row[K.K_ACT]))
                    )
                    if row[K.K_KIND] == K.KIND_INSERT:
                        char_of[op_id] = int(row[K.K_PAYLOAD])
                mark_rows = rows[rows[:, K.K_KIND] == K.KIND_MARK]
                new_table_ids = old_mark_ids + [
                    make_op_id(int(mr[K.K_CTR]), self.actors.actor(int(mr[K.K_ACT])))
                    for mr in mark_rows
                ]
                if len(final_meta) != int(prep["new_lengths"][r]) or len(
                    new_table_ids
                ) != int(prep["new_mark_counts"][r]):
                    raise RuntimeError(
                        "oracle degradation produced inconsistent capacity "
                        f"accounting for replica {self.replica_ids[r]!r}: "
                        f"{len(final_meta)} elements (expected "
                        f"{int(prep['new_lengths'][r])}), {len(new_table_ids)} "
                        f"mark ops (expected {int(prep['new_mark_counts'][r])})"
                    )
                bit_of = {op_id: m for m, op_id in enumerate(new_table_ids)}
                C = self.capacity
                ec = np.zeros(C, np.int32)
                ea = np.zeros(C, np.int32)
                dl = np.zeros(C, bool)
                ch = np.zeros(C, np.int32)
                bd = np.zeros(2 * C, bool)
                bm = np.zeros((2 * C, words), np.uint32)
                for i, item in enumerate(final_meta):
                    ctr_, actor_ = parse_op_id(item.elem_id)
                    ec[i] = ctr_
                    ea[i] = self.actors.id_of(actor_)
                    dl[i] = item.deleted
                    ch[i] = char_of[item.elem_id]
                    for side, p in (("before", 2 * i), ("after", 2 * i + 1)):
                        ops_set = item.get_side(side)
                        if ops_set is not None:
                            bd[p] = True
                            for op_id in ops_set:
                                m = bit_of[op_id]
                                bm[p, m // 32] |= np.uint32(1 << (m % 32))
                elem_ctr[r], elem_act[r] = ec, ea
                deleted[r], chars[r] = dl, ch
                bnd_def[r], bnd_mask[r] = bd, bm
                for m, mr in enumerate(mark_rows, start=n_mk):
                    mark_cols["ctr"][r, m] = int(mr[K.K_CTR])
                    mark_cols["act"][r, m] = int(mr[K.K_ACT])
                    mark_cols["action"][r, m] = int(mr[K.K_MACTION])
                    mark_cols["type"][r, m] = int(mr[K.K_MTYPE])
                    mark_cols["attr"][r, m] = int(mr[K.K_MATTR])
                length_col[r] = len(final_meta)
                mark_count_col[r] = len(new_table_ids)
                # Restore the device placeholder: the staged store must
                # equal what the non-degraded host-op path would stage.
                store.objects[text_obj].clear()
                store.metadata[text_obj] = []
                store.device_objects.add(text_obj)
                for op_id in injected_ids + text_mark_new:
                    store.mark_ops.pop(op_id, None)
            out[r] = pairs
            staged.append((r, store))

        # Everything converted cleanly: publish the device plane, stage the
        # fully-applied stores (fresh version class per replica), commit.
        self.states = self._put_states(
            DocState(
                elem_ctr=elem_ctr,
                elem_act=elem_act,
                deleted=deleted,
                chars=chars,
                bnd_def=bnd_def,
                bnd_mask=bnd_mask,
                mark_ctr=mark_cols["ctr"],
                mark_act=mark_cols["act"],
                mark_action=mark_cols["action"],
                mark_type=mark_cols["type"],
                mark_attr=mark_cols["attr"],
                length=length_col,
                mark_count=mark_count_col,
            )
        )
        self._wcaches = None  # boundary rows rewritten outside the kernels
        for r, store in staged:
            self._store_version_counter += 1
            prep["new_stores"][r] = store
            prep["new_store_versions"][r] = self._store_version_counter
        self._commit(prep)
        return out

    # -- ingestion ----------------------------------------------------------

    def _normalize_batches(
        self, per_replica: Dict[str, Sequence[Change]] | List[Sequence[Change]]
    ) -> List[Sequence[Change]]:
        if isinstance(per_replica, dict):
            batches: List[Sequence[Change]] = [[] for _ in self.replica_ids]
            for name, changes in per_replica.items():
                batches[self.index_of[name]] = changes
            return batches
        batches = list(per_replica)
        if len(batches) != len(self.replica_ids):
            raise ValueError("need one change list per replica")
        return batches

    @_blackbox_on_error
    def apply_changes(self, per_replica: Dict[str, Sequence[Change]] | List[Sequence[Change]]) -> None:
        """Apply a batch of changes to each named replica in one device launch.

        Gate+encode run first for *all* replicas against clock copies; the
        control plane (clocks, lengths, host roots) commits only after the
        device launch, so a causally-unready change in one replica's batch
        can never strand another replica's clock ahead of its device state.

        Text ops integrate via sort-based placement (kernels.
        merge_step_sorted): unbounded insert-run fusion, then the whole
        batch places in O(reference depth) vectorized rounds instead of one
        scan step per op.  Set PERITEXT_MERGE_PATH=scan to force the
        sequential two-phase scan path (debugging/differential runs).
        """
        batches = self._normalize_batches(per_replica)
        prep = self._prepare(batches)
        groups, group_of = prep["groups"], prep["group_of"]
        use_scan = os.environ.get("PERITEXT_MERGE_PATH") == "scan"
        group_sizes, _ = self._account_rows(groups, group_of)

        self._ensure_capacity(prep["need_len"], prep["need_marks"])
        if not any(g["rows"].shape[0] for g in groups):
            self._commit(prep)
            return
        with telemetry.span("ingest.stage"):
            # Split once per distinct group; replicas sharing a stream
            # share it.
            text_rows_list: List[np.ndarray] = []
            mark_rows_list: List[np.ndarray] = []
            for g in groups:
                text_rows, mark_rows = split_rows(g["rows"])
                text_rows_list.append(text_rows)
                mark_rows_list.append(mark_rows)
            # Cost model: a placement round does O(L) x the vector work of
            # one scan step, so sorted wins only when the batch's reference
            # depth D is far below its row count (concurrent merge batches:
            # D is 1-3).  Deep single-writer histories (replaying one
            # actor's whole log, where most inserts reference same-batch
            # elements) degenerate to D ~ L; prepare_sorted_batch re-fuses
            # those for the sequential scan before any padding happens.
            sorted_prep = prepare_sorted_batch(
                text_rows_list,
                max_run=K.MAX_RUN_LEN if use_scan else 0,
                fallback_max_rounds=None
                if use_scan
                else int(os.environ.get("PERITEXT_SORTED_MAX_ROUNDS", "8")),
            )
            if sorted_prep["fell_back"]:
                use_scan = True
                self.stats["scan_fallbacks"] += 1
            mark_pad = bucket_length(max(max(m.shape[0] for m in mark_rows_list), 1))
            g_mark = np.stack([pad_rows(rows, mark_pad) for rows in mark_rows_list])
            # One vectorized gather expands groups to the replica batch.
            text_ops = sorted_prep["text"][group_of]
            mark_ops = g_mark[group_of]
            bufs = sorted_prep["bufs"][group_of]
            rounds = sorted_prep["rounds"][group_of]
            pad_per_group = (
                sorted_prep["text"][:, :, K.K_KIND] == K.KIND_PAD
            ).sum(axis=1) + (g_mark[:, :, K.K_KIND] == K.KIND_PAD).sum(axis=1)
            self.stats["rows_padded"] += int((pad_per_group * group_sizes).sum())
        # Frontier-bounded window merge (ISSUE 12): when the host census can
        # bound every op's reach, gather the window, merge O(window), and
        # scatter back — the full-table path stays the adaptive fallback.
        wplan = None if use_scan else self._window_plan(prep)
        # ONE batched host->device transfer for the launch's op tensors
        # (per-array device_put overhead dominates small windowed
        # launches); retries and the census-rejection relaunch reuse them.
        # device_put may return before the copy lands: the span can end at
        # the enqueue.
        with telemetry.span("ingest.put"):
            ranks = self._ranks_jax()
            if wplan is not None:
                d_text, d_rounds, d_mark, d_bufs, d_wstart, d_whull = self._put(
                    (text_ops, rounds, mark_ops, bufs, wplan["starts"], wplan["hulls"])
                )
            else:
                d_text, d_rounds, d_mark, d_bufs = self._put(
                    (text_ops, rounds, mark_ops, bufs)
                )

        strict = os.environ.get("PERITEXT_STRICT_COMMIT") == "1"
        if wplan is not None:

            def wattempt():
                faults.fire("device_launch")
                st, wrec = K.merge_step_sorted_windowed_batch(
                    self.states,
                    d_wstart,
                    d_whull,
                    d_text,
                    d_rounds,
                    sorted_prep["num_rounds"],
                    d_mark,
                    ranks,
                    d_bufs,
                    sorted_prep["maxk"],
                    wplan["w_cap"],
                )
                faults.fire("device_readback")
                # The census-verdict + mirror readback IS this path's
                # barrier (the windowed merge trades launch pipelining for
                # O(window) compute; the readback is window-sized).
                wrec_np = jax.device_get(wrec)
                return (st, wrec_np), st.length

            try:
                new_states, wrec_np = self._run_launch(wattempt, needs_barrier=strict)
            except DeviceLaunchError:
                if not _degrade_enabled():
                    raise
                self._degrade_apply(prep)
                return
            if bool(wrec_np["wok"].all()):
                self.states = new_states
                self.stats["launches"] += 1
                self.stats["windowed_launches"] = (
                    self.stats.get("windowed_launches", 0) + 1
                )
                if telemetry.enabled:
                    telemetry.flow_steps(path="windowed", window=int(wplan["w_cap"]))
                    telemetry.counter("ingest.launches")
                    telemetry.counter("ingest.path.sorted")
                    telemetry.counter("ingest.path.windowed")
                    telemetry.counter(
                        "ingest.h2d_bytes",
                        int(
                            text_ops.nbytes
                            + mark_ops.nbytes
                            + bufs.nbytes
                            + rounds.nbytes
                        ),
                    )
                    telemetry.counter(
                        "ingest.d2h_bytes",
                        int(sum(v.nbytes for v in wrec_np.values())),
                    )
                self._wcaches = None
                self._mirror_commit(wplan, wrec_np, prep)
                self._commit(prep)
                return
            # Device census check rejected the window: relaunch full-table.
            self._window_fallback(
                d2h_bytes=int(sum(v.nbytes for v in wrec_np.values()))
            )

        def attempt():
            faults.fire("device_launch")
            if use_scan:
                st = K.merge_step_fused_batch(
                    self.states, d_text, d_mark, ranks, d_bufs
                )
            else:
                st = K.merge_step_sorted_batch(
                    self.states,
                    d_text,
                    d_rounds,
                    sorted_prep["num_rounds"],
                    d_mark,
                    ranks,
                    d_bufs,
                    sorted_prep["maxk"],
                )
            return st, st.length

        # PERITEXT_STRICT_COMMIT=1: execution barrier before the
        # control-plane commit.  JAX dispatch is async, so by default a
        # launch that later fails on-device can leave committed clocks
        # ahead of the state (surfacing at the next readback).  Strict mode
        # trades pipelining for commit-after-*execution* — use it on flaky
        # backends.  The barrier runs inside the
        # retry attempt, so a readback failure consumes retry budget and
        # leaves the committed state untouched.  (``strict`` was resolved
        # above, before the windowed branch.)
        try:
            new_states = self._run_launch(attempt, needs_barrier=strict)
        except DeviceLaunchError:
            if not _degrade_enabled():
                raise  # committed state untouched: nothing was assigned
            self._degrade_apply(prep)
            return
        self.states = new_states
        # "launches" counts SUCCESSFUL kernel launches on every ingest path
        # (failed attempts show up in launch_retries; degraded batches in
        # degraded_batches), so launch/batch ratios are path-independent.
        self.stats["launches"] += 1
        if telemetry.enabled:
            telemetry.counter("ingest.launches")
            telemetry.counter(
                "ingest.path.scan" if use_scan else "ingest.path.sorted"
            )
            telemetry.counter(
                "ingest.h2d_bytes",
                int(
                    text_ops.nbytes
                    + mark_ops.nbytes
                    + bufs.nbytes
                    + rounds.nbytes
                ),
            )
        # Non-patched merges rewrite boundary rows without maintaining the
        # patched path's winner cache.
        self._wcaches = None
        self._commit(prep)

    # -- patch-emitting ingestion (the incremental codepath) ----------------

    @staticmethod
    def _patch_chunk(n: int) -> int:
        """R-chunk size for patch-record launches (opt-in memory valve,
        PERITEXT_PATCH_CHUNK), equalized so the jit caches hold at most two
        program shapes (the even chunks and one smaller tail)."""
        raw = os.environ.get("PERITEXT_PATCH_CHUNK", "0")
        try:
            chunk = int(raw)
        except ValueError:
            raise ValueError(f"PERITEXT_PATCH_CHUNK must be an integer, got {raw!r}")
        if chunk < 0:
            raise ValueError(f"PERITEXT_PATCH_CHUNK must be >= 0, got {chunk}")
        chunk = chunk or n
        return math.ceil(n / math.ceil(n / chunk))

    @staticmethod
    def _cand_cap(prep: Dict[str, Any]) -> int:
        """Static candidate-axis width for the compact readback: defined
        boundary slots never exceed 2x the mark table (anchor writes are
        the only first definitions), and the host mirrors every replica's
        post-batch mark count — a sound, pow2-bucketed bound."""
        return bucket_length(
            2 * int(np.asarray(prep["new_mark_counts"]).max(initial=0)) + 2,
            minimum=8,
        )

    def _span_overflow(
        self, record_chunks: List[Dict[str, np.ndarray]], span_cap: int
    ) -> bool:
        """Did any mark row's true span count exceed the compact readback
        capacity?  If so, tally it and grow the universe's cap (pow2) to
        the observed maximum so subsequent batches launch wide enough."""
        overflow = max(
            (int(rec["mcount"].max(initial=0)) for rec in record_chunks),
            default=0,
        )
        if overflow <= span_cap:
            return False
        self.stats["readback_overflows"] = (
            self.stats.get("readback_overflows", 0) + 1
        )
        self._span_cap = bucket_length(overflow, minimum=1)
        if "PERITEXT_PATCH_SPAN_CAP" not in os.environ:
            # An env-pinned cap owns its universes (tests, A/B legs):
            # their deliberate overflows must not inflate the floor every
            # later un-pinned universe starts from.
            TpuUniverse._span_cap_floor = max(
                TpuUniverse._span_cap_floor, self._span_cap
            )
        if telemetry.enabled:
            telemetry.counter("ingest.readback_overflow")
        _log.info(
            "compact patch readback overflowed (%d spans > cap %d); "
            "re-reading via planes and growing the cap to %d",
            overflow,
            span_cap,
            self._span_cap,
        )
        return True

    @_blackbox_on_error
    def apply_changes_with_patches(
        self,
        per_replica: Dict[str, Sequence[Change]] | List[Sequence[Change]],
        with_positions: bool = False,
    ) -> Dict[str, List[Any]]:
        """Causally-gated ingestion that also emits the reference Patch
        stream per replica (micromerge.ts:25-30).

        Default path: the patch-emitting sorted merge (kernels.
        merge_step_sorted_patched) — placement rounds for text, a
        compact-delta scan over mark rows only, analytic insert/delete
        records.  PERITEXT_PATCH_PATH=dense forces the full-plane-carry
        mark scan (the A/B baseline); deep batches fall back to the
        faithful interleaved per-op scan, as does PERITEXT_MERGE_PATH=scan
        / PERITEXT_PATCH_PATH=scan.  Every path emits the same
        byte-identical reference stream (micromerge dual-path invariant,
        test/micromerge.ts:84-85).

        PERITEXT_PATCH_READBACK selects the record transfer format on
        every path: "compact" (default) reads back device-compacted span
        run tables (output-proportional D2H), "planes" the full per-slot
        planes (the A/B baseline).  Both formats assemble byte-identical
        streams; a compact launch whose span counts overflow the adaptive
        cap re-reads that batch via planes.

        With ``with_positions`` each replica's list holds ``(pos, patch)``
        pairs instead of bare patches, where ``pos`` is the patch's op's
        flat index in that replica's gated (ordered, deduplicated) batch
        stream — the serving plane (runtime/serve.py) uses the ranges to
        split one continuous-batched launch's stream back into exact
        per-submission patch lists.  The pair list is the same stream in
        the same order; stripping positions yields the default return.

        The key of every program launched lands in ``launch_keys``; after
        :meth:`warm_patched` the launch keeps to the warmed shapes.
        """
        self.launch_keys = []
        batches = self._normalize_batches(per_replica)
        prep = self._prepare(batches)
        groups, group_of = prep["groups"], prep["group_of"]

        group_sizes, row_counts = self._account_rows(groups, group_of)
        max_rows = int(row_counts.max(initial=0))

        self._ensure_capacity(prep["need_len"], prep["need_marks"])
        # Host-object patches (root/nested-map and host-list ops) were
        # emitted during the _prepare dry-run, tagged with each op's flat
        # position in the batch stream; device patches get the same tags so
        # the merged stream is in true op order (what an incremental oracle
        # consuming this delivery order would emit).
        # Host patch lists are shared across a version class (one immutable
        # decode per class, from the _prepare dry-run); each replica
        # materializes its own mutation-safe copy lazily via the cheap
        # frozen-structure copy — deepcopy here ran once per patch per
        # REPLICA per call and scaled with the fleet.
        def host_patches_for(r: int) -> List[Any]:
            return [
                (pos, _copy_jsonlike(p)) for pos, p in prep["host_patches"].get(r, [])
            ]

        if max_rows == 0:
            self._commit(prep)
            return {
                name: _strip_pos(
                    sorted(host_patches_for(r), key=lambda t: t[0]), with_positions
                )
                for r, name in enumerate(self.replica_ids)
            }

        use_scan = (
            os.environ.get("PERITEXT_MERGE_PATH") == "scan"
            or os.environ.get("PERITEXT_PATCH_PATH") == "scan"
        )
        sorted_prep = None
        if not use_scan:
            # The first half of this path's ingest.stage; _patched_sorted
            # pads and gathers in the second.
            with telemetry.span("ingest.stage"):
                text_rows_list: List[np.ndarray] = []
                mark_rows_list: List[np.ndarray] = []
                text_pos_list: List[np.ndarray] = []
                mark_pos_list: List[np.ndarray] = []
                for g in groups:
                    rows = g["rows"]
                    rp = np.asarray(g["row_pos"])
                    is_mark = rows[:, K.K_KIND] == K.KIND_MARK
                    text_rows_list.append(rows[~is_mark])
                    mark_rows_list.append(rows[is_mark])
                    text_pos_list.append(rp[~is_mark])
                    mark_pos_list.append(rp[is_mark])
                sorted_prep = prepare_sorted_batch(
                    text_rows_list,
                    max_run=0,
                    fallback_max_rounds=int(
                        os.environ.get("PERITEXT_SORTED_MAX_ROUNDS", "8")
                    ),
                    pos_list=text_pos_list,
                    restack_on_fallback=False,
                )
                multi_need = self._multi_group_need(mark_rows_list)
            if sorted_prep["fell_back"]:
                use_scan = True
                self.stats["scan_fallbacks"] += 1
            elif multi_need > K.PATCH_GROUP_K:
                # The cached patch scan resolves allowMultiple groups over
                # at most PATCH_GROUP_K compacted columns; a larger group
                # must take the exact interleaved path.
                use_scan = True
                self.stats["multi_group_fallbacks"] = (
                    self.stats.get("multi_group_fallbacks", 0) + 1
                )
        if not use_scan:
            return self._patched_sorted(
                prep,
                host_patches_for,
                sorted_prep,
                mark_rows_list,
                mark_pos_list,
                group_sizes,
                multi_need,
                with_positions=with_positions,
            )
        return self._patched_scan(
            prep, host_patches_for, group_sizes, max_rows, with_positions=with_positions
        )

    def _patched_scan(
        self, prep, host_patches_for, group_sizes, max_rows, with_positions=False
    ):
        """The faithful interleaved per-op patch path (one scan step per
        op; the reference's asymptotics, kept as the deep-batch fallback
        and the PERITEXT_PATCH_PATH=scan differential leg)."""
        groups, group_of = prep["groups"], prep["group_of"]
        with telemetry.span("ingest.stage"):
            pad = bucket_length(max_rows)
            g_ops = np.stack([pad_rows(g["rows"], pad) for g in groups])
            ops = g_ops[group_of]
        with telemetry.span("ingest.put"):
            d_ops = self._put(ops)
            ranks = self._ranks_jax()
            multi = _multi_jax(self._rep_sharding)
        pad_per_group = (g_ops[:, :, K.K_KIND] == K.KIND_PAD).sum(axis=1)
        self.stats["rows_padded"] += int((pad_per_group * group_sizes).sum())

        # The per-op patch records materialize [R, ops, 2C] slot planes; at
        # large R that dwarfs the state, so launch over R-chunks (opt-in,
        # PERITEXT_PATCH_CHUNK) and read each chunk's records back to host
        # before the next chunk's launch.  Device state is immutable, so a
        # mid-chunk failure rolls back to the pre-batch pytree and nothing
        # commits (same atomicity contract as the fast path).
        n = len(self.replica_ids)
        chunk = self._patch_chunk(n)

        # The chunked loop is one resilient launch unit: each chunk's record
        # readback happens inside the attempt, so a mid-loop failure simply
        # discards the partial results (device state is immutable — the
        # committed pytree is untouched until the whole attempt succeeds).
        readback = _patch_readback()
        span_cap = self._span_cap

        def make_attempt(rb: str):
            def attempt():
                state_slices = []
                record_chunks: List[Dict[str, np.ndarray]] = []
                for i in range(0, n, chunk):
                    sl = slice(i, min(i + chunk, n))
                    faults.fire("device_launch")
                    self.launch_keys.append(("scan", rb, sl.stop - sl.start, ops.shape))
                    st, records = K.apply_ops_patched_batch(
                        jax.tree.map(lambda x: x[sl], self.states),
                        d_ops[sl],
                        ranks,
                        multi,
                        readback=rb,
                        span_cap=span_cap,
                    )
                    state_slices.append(st)
                    faults.fire("device_readback")
                    # The device_get barrier IS the record D2H transfer —
                    # span it here so the critical-path report attributes
                    # readback time separately from device dispatch.
                    with telemetry.span("ingest.readback", readback=rb, chunk=i):
                        if telemetry.enabled:
                            telemetry.flow_steps(readback=rb)
                        record_chunks.append(jax.device_get(records))
                states = (
                    state_slices[0]
                    if len(state_slices) == 1
                    else jax.tree.map(
                        lambda *xs: jax.numpy.concatenate(xs), *state_slices
                    )
                )
                return (states, record_chunks), states.length

            return attempt

        try:
            new_states, record_chunks = self._run_launch(make_attempt(readback))
            launches = len(record_chunks)  # successful chunk launches
            d2h = sum(v.nbytes for rec in record_chunks for v in rec.values())
            if readback == "compact" and self._span_overflow(record_chunks, span_cap):
                # Some mark row emitted more spans than the compact tables
                # hold; re-read this batch via the planes format (device
                # state is immutable — a relaunch recomputes byte-identical
                # records) and grow the cap for the next batch.
                readback = "planes"
                new_states, record_chunks = self._run_launch(make_attempt("planes"))
                launches += len(record_chunks)
                d2h += sum(v.nbytes for rec in record_chunks for v in rec.values())
        except DeviceLaunchError:
            if not _degrade_enabled():
                raise
            pairs = self._degrade_apply(prep)
            return {
                name: _strip_pos(pairs[r], with_positions)
                for r, name in enumerate(self.replica_ids)
            }
        self.states = new_states
        self.stats["launches"] += launches
        if telemetry.enabled:
            telemetry.counter("ingest.launches", launches)
            telemetry.counter("ingest.path.scan")
            telemetry.counter("ingest.readback." + readback)
            telemetry.counter("ingest.h2d_bytes", int(ops.nbytes))
            telemetry.counter("ingest.d2h_bytes", int(d2h))
            # Record-readback accounting (the span covering the actual
            # D2H barrier lives inside the attempt closure above).
            telemetry.record(
                "ingest.readback", fmt=readback, d2h_bytes=int(d2h)
            )
        # The interleaved path doesn't maintain the winner cache.
        self._wcaches = None
        self._commit(prep)
        with telemetry.span("ingest.assemble", replicas=len(self.replica_ids)):
            if telemetry.enabled:
                telemetry.flow_steps()
            tables = self._batch_mark_op_table()
            out: Dict[str, List[Dict[str, Any]]] = {}
            for r, name in enumerate(self.replica_ids):
                rec = record_chunks[r // chunk]
                g = groups[group_of[r]]
                dev = assemble_patches(
                    rec, r % chunk, ops[r], tables[r], self.attrs, row_pos=g["row_pos"]
                )
                merged = sorted(dev + host_patches_for(r), key=lambda t: t[0])
                out[name] = _strip_pos(merged, with_positions)
        return out

    def _stage_sorted(
        self, sorted_prep, mark_rows_list, mark_pos_list, group_of
    ) -> Dict[str, Any]:
        """Second half of the patched path's ``ingest.stage``: pad the
        mark rows (and, once warmed, the char buffer to ``WARM_BUF``) and
        gather every per-group tensor to its replicas."""
        mark_pad = bucket_length(
            max(max((m.shape[0] for m in mark_rows_list), default=1), 1)
        )
        bufs = sorted_prep["bufs"]
        if self._warmed and bufs.shape[1] < WARM_BUF:
            bufs = np.pad(bufs, ((0, 0), (0, WARM_BUF - bufs.shape[1])))
        g_mark = np.stack([pad_rows(m, mark_pad) for m in mark_rows_list])
        g_mark_pos = np.stack(
            [
                np.pad(
                    p.astype(np.int64),
                    (0, mark_pad - p.shape[0]),
                    constant_values=TIME_PAD,
                )
                for p in mark_pos_list
            ]
        ).astype(np.int32)
        return {
            "g_mark": g_mark,
            "g_mark_pos": g_mark_pos,
            "text": sorted_prep["text"][group_of],
            "rounds": sorted_prep["rounds"][group_of],
            "bufs": bufs[group_of],
            "text_pos": sorted_prep["text_pos"][group_of],
            "mark": g_mark[group_of],
            "mark_pos": g_mark_pos[group_of],
        }

    def _sorted_attempt(
        self, d_ops, wdev, statics, wc, rb: str, keys: List[tuple],
        windowed: bool = False,
    ):
        """One launch attempt of the patch-emitting sorted merge over the
        device op tensors ``d_ops`` (text, rounds, bufs, text_pos, mark,
        mark_pos) — windowed over the device window plan ``wdev`` (starts,
        hulls, vis_base, vis_after, then the static width), else
        full-table in R-chunks.  Each launch appends its program's key to
        ``keys`` (``launch_keys`` / ``warmed_keys``)."""
        d_text, d_rounds, d_bufs, d_tpos, d_mark, d_mpos = d_ops
        n = len(self.replica_ids)
        chunk = self._patch_chunk(n)
        ranks = self._ranks_jax()
        multi = _multi_jax(self._rep_sharding)
        kw = dict(statics)
        num_rounds = kw.pop("num_rounds")
        maxk = kw.pop("maxk")
        key = (
            rb, wc is not None, maxk, tuple(sorted(kw.items())),
            tuple(a.shape for a in d_ops), ranks.shape,
            (self.capacity, self.max_mark_ops),
        )

        def attempt():
            if windowed:
                # Frontier-bounded window merge: one launch over the
                # gathered [R, w_cap] windows (never chunked — a window
                # plan is only produced with the chunk valves unset).
                d_wstart, d_whull, d_wvb, d_wva, w_cap = wdev
                faults.fire("device_launch")
                keys.append(("windowed", w_cap, n) + key)
                st, records = K.merge_step_sorted_patched_windowed_batch(
                    self.states, d_wstart, d_whull, d_wvb, d_wva,
                    d_text, d_rounds, num_rounds, d_mark, ranks, d_bufs, multi,
                    d_tpos, d_mpos, maxk, w_cap,
                    wcache_in=wc, readback=rb, **kw,
                )
                wcache = records.pop("wcache", None)
                faults.fire("device_readback")
                with telemetry.span("ingest.readback", readback=rb, windowed=1):
                    if telemetry.enabled:
                        telemetry.flow_steps(readback=rb)
                    # One batched D2H transfer for all record planes.
                    rec_np = jax.device_get(records)
                return (st, [rec_np], wcache), st.length
            state_slices = []
            record_chunks: List[Dict[str, np.ndarray]] = []
            wcache_slices = []
            for i in range(0, n, chunk):
                sl = slice(i, min(i + chunk, n))
                faults.fire("device_launch")
                keys.append(("full", sl.stop - sl.start) + key)
                st, records = K.merge_step_sorted_patched_batch(
                    jax.tree.map(lambda x: x[sl], self.states),
                    d_text[sl], d_rounds[sl], num_rounds, d_mark[sl], ranks,
                    d_bufs[sl], multi, d_tpos[sl], d_mpos[sl], maxk,
                    wcache_in=None if wc is None else wc[sl], readback=rb, **kw,
                )
                state_slices.append(st)
                # Keep the cache on device — reading it back would cost
                # more than the init it saves.
                wcache_slices.append(records.pop("wcache", None))
                faults.fire("device_readback")
                # The device_get barrier IS the record D2H transfer —
                # span it here so the critical-path report attributes
                # readback time separately from device dispatch.
                with telemetry.span("ingest.readback", readback=rb, chunk=i):
                    if telemetry.enabled:
                        telemetry.flow_steps(readback=rb)
                    record_chunks.append(jax.device_get(records))
            states = (
                state_slices[0]
                if len(state_slices) == 1
                else jax.tree.map(lambda *xs: jax.numpy.concatenate(xs), *state_slices)
            )
            if all(w is not None for w in wcache_slices):
                wcache = (
                    wcache_slices[0]
                    if len(wcache_slices) == 1
                    else jax.numpy.concatenate(wcache_slices)
                )
            else:
                # Cacheless mark-free launch: rows unchanged but slots
                # re-permuted, so a stale cache must not survive.
                wcache = None
            return (states, record_chunks, wcache), states.length

        return attempt

    def _sorted_statics(
        self, sorted_prep, has_marks: bool, multi_need: int, cand_cap: int
    ) -> Dict[str, Any]:
        """The patched sorted merge's static specializations (plus the
        traced round count) for one batch; once warmed, the warmed ones."""
        warmed = self._warmed
        return {
            "num_rounds": sorted_prep["num_rounds"],
            "maxk": max(sorted_prep["maxk"], WARM_RUN) if warmed else sorted_prep["maxk"],
            "has_marks": has_marks or warmed,
            "mode": (
                "dense"
                if os.environ.get("PERITEXT_PATCH_PATH") == "dense"
                else "delta"
            ),
            "group_k": bucket_length(multi_need, minimum=1),
            "has_multi": multi_need > 0 or warmed,
            # The delta scan's carried batch-winner table only needs the
            # LIVE mark-type registry (pow2-bucketed, like group_k): valid
            # ops' type ids are < NUM_MARK_TYPES, and the cache plane's
            # padding types (MAX_MARK_TYPES) pass through its final
            # compose untouched.
            "t_act": min(
                bucket_length(schema.NUM_MARK_TYPES, minimum=1),
                schema.MAX_MARK_TYPES,
            ),
            "span_cap": self._span_cap,
            # Warmed: every defined slot a replica can hold.
            "cand_cap": (
                max(cand_cap, bucket_length(2 * self.max_mark_ops + 2))
                if warmed
                else cand_cap
            ),
        }

    def _current_wcache(self, multi) -> Any:
        """The persisted winner cache when it matches the current shapes
        AND the actor registry it was built under (interning a new actor
        renumbers every rank the cache stores), else None."""
        wc = self._wcaches
        if wc is not None and (
            self._wcaches_actors != len(self.actors.actors)
            or wc.shape
            != (len(self.replica_ids), 2 * self.capacity, int(np.asarray(multi).shape[0]), 4)
        ):
            wc = None
        return wc

    def warm_patched(self) -> int:
        """Compile, on this universe's device(s), every program a patched
        sorted launch takes from now on for a batch within the warmed
        shapes (``WARM_RUN``): the full-table merge, the windowed merge
        at every width the census can then pick, and the census's reach
        bound.  Each runs once over
        all-pad op rows against the committed state — nothing commits —
        and its key joins ``warmed_keys``, which a launch outside the set
        misses (the serving plane counts those as ``serve.cold_shapes``).
        The persisted winner cache is dropped and no longer kept: a
        windowed launch would gather and scatter the whole ``[R, 2C, T,
        4]`` cache to splice its window back.  Warm after loading the
        documents: a grown capacity, mark table, span cap or actor table
        changes the shapes.  Returns the number of programs run."""
        self._warmed = True
        self._wcaches = None
        n = len(self.replica_ids)
        none = [np.zeros((0, K.OP_FIELDS), np.int32)]
        sorted_prep = prepare_sorted_batch(none, max_run=0, pos_list=[np.zeros(0, np.int64)])
        staged = self._stage_sorted(
            sorted_prep, none, [np.zeros(0, np.int64)], np.zeros(n, np.int32)
        )
        statics = self._sorted_statics(sorted_prep, False, 0, self._cand_cap(
            {"new_mark_counts": np.asarray(self.mark_counts)}))
        d_ops = self._put(tuple(staged[k] for k in (
            "text", "rounds", "bufs", "text_pos", "mark", "mark_pos")))
        rb = _patch_readback()
        keys: List[tuple] = []
        runs = [(None, False)]
        zero = np.zeros(n, np.int32)
        for w_cap in _warm_windows(self.capacity):
            runs.append((self._put((zero, zero, zero, zero)) + (w_cap,), True))
        for wdev, windowed in runs:
            out = self._sorted_attempt(d_ops, wdev, statics, None, rb, keys, windowed)()
            jax.block_until_ready(out[0][0])
        if len(runs) > 1:  # the census runs, and with it the reach bound
            self._reach_bound(none, [0], np.zeros(n, np.int32))
            keys.append(("window_reach", n, self.capacity))
        self.warmed_keys.update(keys)
        return len(keys)

    def _patched_sorted(
        self,
        prep,
        host_patches_for,
        sorted_prep,
        mark_rows_list,
        mark_pos_list,
        sizes,
        multi_need: int = 0,
        with_positions: bool = False,
    ):
        """The patch-emitting sorted merge: placement rounds + mark-only
        scan + analytic text records (kernels.merge_step_sorted_patched).
        Record planes are [R, marks, 2C] — only mark rows, not every op —
        so the memory valve matters less, but PERITEXT_PATCH_CHUNK still
        applies.  Under the default compact readback
        (PERITEXT_PATCH_READBACK) the planes never cross D2H at all: the
        launch compacts them to [R, marks, span_cap] run tables and host
        assembly consumes the spans vectorized
        (assemble_patches_sorted_compact); overflow of the adaptive cap
        falls back to a planes re-read for the batch.

        The mark-row scan runs as the compact-delta variant by default;
        PERITEXT_PATCH_PATH=dense forces the full-plane-carry variant for
        A/B (both byte-identical).  ``multi_need`` (the host census's
        largest targeted allowMultiple group, already gated under
        PATCH_GROUP_K by the caller) statically sizes the delta scan's
        group resolution — a batch with no multi ops compiles without the
        per-step group machinery entirely, unless the universe is warmed
        (:meth:`warm_patched`)."""
        groups, group_of = prep["groups"], prep["group_of"]

        with telemetry.span("ingest.stage"):
            staged = self._stage_sorted(
                sorted_prep, mark_rows_list, mark_pos_list, group_of
            )
        g_mark, g_mark_pos = staged["g_mark"], staged["g_mark_pos"]
        host_ops = tuple(
            staged[k] for k in ("text", "rounds", "bufs", "text_pos", "mark", "mark_pos")
        )
        wplan = self._window_plan(prep)
        # ONE batched host->device transfer for the whole launch's op
        # tensors (a device_put per array cost ~0.1ms fixed overhead each
        # on the build box — at windowed single-op latencies that was the
        # dominant term); retries and overflow relaunches reuse the same
        # device arrays.
        with telemetry.span("ingest.put"):
            self._ranks_jax()  # the rank table's upload, when it changed
            multi = _multi_jax(self._rep_sharding)
            if wplan is not None:
                put = self._put(
                    host_ops
                    + (wplan["starts"], wplan["hulls"], wplan["vis_base"], wplan["vis_after"])
                )
                d_ops, wdev = put[:6], tuple(put[6:]) + (wplan["w_cap"],)
            else:
                d_ops, wdev = self._put(host_ops), None
        pad_per_group = (sorted_prep["text"][:, :, K.K_KIND] == K.KIND_PAD).sum(
            axis=1
        ) + (g_mark[:, :, K.K_KIND] == K.KIND_PAD).sum(axis=1)
        self.stats["rows_padded"] += int((pad_per_group * sizes).sum())

        n = len(self.replica_ids)
        chunk = self._patch_chunk(n)
        # Static mark-free fast path: a pure-typing batch (no real mark
        # rows anywhere) compiles without the winner-cache init or the
        # mark scan.
        has_marks = any(m.shape[0] for m in mark_rows_list)
        statics = self._sorted_statics(
            sorted_prep, has_marks, multi_need, self._cand_cap(prep)
        )
        mode = statics["mode"]
        # Thread the persisted winner cache when it is current.
        wc = None if self._warmed else self._current_wcache(multi)
        readback = _patch_readback()
        span_cap = statics["span_cap"]

        def make_attempt(rb: str, windowed: bool = False):
            return self._sorted_attempt(
                d_ops, wdev, statics, wc, rb, self.launch_keys, windowed
            )

        use_window = wplan is not None
        try:
            new_states, record_chunks, wcache = self._run_launch(
                make_attempt(readback, use_window)
            )
            if use_window and not bool(record_chunks[0]["wok"].all()):
                # The device census check rejected the window (stale
                # mirror): discard the windowed result — nothing was
                # committed — and relaunch the full-table path.  (This
                # path's dispatch window already spans both launches, so no
                # extra elapsed time is passed.)
                self._window_fallback(
                    launches=len(record_chunks),
                    d2h_bytes=int(
                        sum(
                            v.nbytes
                            for rec in record_chunks
                            for v in rec.values()
                        )
                    ),
                )
                use_window = False
                new_states, record_chunks, wcache = self._run_launch(
                    make_attempt(readback)
                )
            launches = len(record_chunks)  # successful chunk launches
            d2h = sum(v.nbytes for rec in record_chunks for v in rec.values())
            if readback == "compact" and self._span_overflow(record_chunks, span_cap):
                # Overflowed span tables cannot reconstruct the stream;
                # re-read this batch via the planes format (byte-identical
                # records recomputed from the immutable committed state)
                # and let the grown cap cover the next batch.
                readback = "planes"
                new_states, record_chunks, wcache = self._run_launch(
                    make_attempt("planes", use_window)
                )
                launches += len(record_chunks)
                d2h += sum(v.nbytes for rec in record_chunks for v in rec.values())
        except DeviceLaunchError:
            if not _degrade_enabled():
                raise  # committed state untouched: attempts never assign
            pairs = self._degrade_apply(prep)
            return {
                name: _strip_pos(pairs[r], with_positions)
                for r, name in enumerate(self.replica_ids)
            }
        if use_window and os.environ.get("PERITEXT_WINDOW_CHECK") == "1":
            # Paranoid differential (debug/CI drill): recompute this batch
            # on the full-table path from the same committed state and
            # fail loudly on any plane divergence — turns a silent census
            # bug into an immediate, batch-precise report.
            ref_states, _, _ = self._run_launch(make_attempt(readback))
            self._assert_states_match(ref_states, new_states, wplan, prep)
        self.states = new_states
        self.stats["launches"] += launches
        if use_window:
            self.stats["windowed_launches"] = (
                self.stats.get("windowed_launches", 0) + 1
            )
            self._mirror_commit(wplan, record_chunks[0], prep)
        if telemetry.enabled:
            telemetry.counter("ingest.launches", launches)
            telemetry.counter("ingest.path." + mode)
            if use_window:
                telemetry.counter("ingest.path.windowed")
                telemetry.flow_steps(path="windowed", window=int(wplan["w_cap"]))
            telemetry.counter("ingest.readback." + readback)
            telemetry.counter(
                "ingest.h2d_bytes", int(sum(a.nbytes for a in host_ops))
            )
            telemetry.counter("ingest.d2h_bytes", int(d2h))
            # Record-readback accounting (the span covering the actual
            # D2H barrier lives inside the attempt closure above).
            telemetry.record(
                "ingest.readback", fmt=readback, d2h_bytes=int(d2h)
            )
        self._wcaches = None if self._warmed else wcache
        if self._wcaches is not None:
            # ranks() used by this launch reflect the post-_prepare
            # registry; key the cache to it.
            self._wcaches_actors = len(self.actors.actors)
        self._commit(prep)
        with telemetry.span("ingest.assemble", replicas=len(self.replica_ids)):
            if telemetry.enabled:
                telemetry.flow_steps()
            tables = self._batch_mark_op_table()
            out: Dict[str, List[Dict[str, Any]]] = {}
            assemble = (
                assemble_patches_sorted_compact
                if readback == "compact"
                else assemble_patches_sorted
            )
            for r, name in enumerate(self.replica_ids):
                rec = record_chunks[r // chunk]
                gi = int(group_of[r])
                dev = assemble(
                    rec,
                    r % chunk,
                    sorted_prep["text"][gi],
                    sorted_prep["text_pos"][gi],
                    sorted_prep["bufs"][gi],
                    g_mark[gi],
                    g_mark_pos[gi],
                    tables[r],
                    self.attrs,
                )
                merged = sorted(dev + host_patches_for(r), key=lambda t: t[0])
                out[name] = _strip_pos(merged, with_positions)
        return out

    # -- materialization ----------------------------------------------------

    def _build_mark_table(
        self,
        ctr: np.ndarray,
        act: np.ndarray,
        action: np.ndarray,
        mtype: np.ndarray,
        attr: np.ndarray,
    ) -> Dict[str, Dict[str, Any]]:
        table: Dict[str, Dict[str, Any]] = {}
        for m in range(ctr.shape[0]):
            op_id = make_op_id(int(ctr[m]), self.actors.actor(int(act[m])))
            op: Dict[str, Any] = {
                "opId": op_id,
                "action": "addMark" if action[m] == 0 else "removeMark",
                "markType": schema.ALL_MARKS[int(mtype[m])],
            }
            attrs = self.attrs.decode(int(attr[m]))
            if attrs is not None:
                op["attrs"] = attrs
            table[op_id] = op
        return table

    def _mark_op_table(self, state: DocState) -> Dict[str, Dict[str, Any]]:
        n = int(state.mark_count)
        return self._build_mark_table(
            np.asarray(state.mark_ctr[:n]),
            np.asarray(state.mark_act[:n]),
            np.asarray(state.mark_action[:n]),
            np.asarray(state.mark_type[:n]),
            np.asarray(state.mark_attr[:n]),
        )

    def _batch_mark_op_table(self) -> List[Dict[str, Dict[str, Any]]]:
        """Per-replica mark tables from one batched readback, deduped so
        replicas with identical tables share one decoded object (the common
        fleet case — and the dedup key is what lets span decoding share its
        resolution cache across the batch)."""
        ctr = np.asarray(self.states.mark_ctr)
        act = np.asarray(self.states.mark_act)
        action = np.asarray(self.states.mark_action)
        mtype = np.asarray(self.states.mark_type)
        attr = np.asarray(self.states.mark_attr)
        counts = np.asarray(self.states.mark_count)
        cache: Dict[bytes, Dict[str, Dict[str, Any]]] = {}
        tables = []
        for r in range(len(self.replica_ids)):
            n = int(counts[r])
            key = b"".join(
                a[r, :n].tobytes() for a in (ctr, act, action, mtype, attr)
            )
            t = cache.get(key)
            if t is None:
                t = self._build_mark_table(
                    ctr[r, :n], act[r, :n], action[r, :n], mtype[r, :n], attr[r, :n]
                )
                cache[key] = t
            tables.append(t)
        return tables

    @staticmethod
    def _codepoints_to_str(codepoints: np.ndarray) -> str:
        """Vectorized codepoint-array -> str (module helper; surrogatepass
        so the batch decode accepts exactly what chr() accepts)."""
        return _codepoints_to_str(codepoints)

    def _spans_from_arrays(
        self,
        mask_np: np.ndarray,
        has_np: np.ndarray,
        deleted: np.ndarray,
        chars: np.ndarray,
        table: Dict[str, Dict[str, Any]],
        mark_cache: Optional[Dict[Any, Dict[str, Any]]] = None,
    ) -> List[Dict[str, Any]]:
        """Segment one replica's flattened arrays into reference spans.

        Tombstones carry no text, and the oracle's run coalescer merges
        adjacent spans with deep-equal marks (peritext.ts:438-451), so the
        span structure is fully determined by the *visible* elements' mask
        rows: segment boundaries are where consecutive visible elements'
        resolved bitsets differ (a numpy diff), never a per-character loop.
        """
        op_ids = list(table)

        def decode_row(row: np.ndarray) -> frozenset:
            return frozenset(
                op_id
                for m, op_id in enumerate(op_ids)
                if row[m // 32] >> (m % 32) & 1
            )

        vis = np.flatnonzero(~deleted)
        if vis.size == 0:
            return []
        v_has = has_np[vis]
        v_mask = mask_np[vis]
        v_chars = chars[vis]
        change = np.empty(vis.size, bool)
        change[0] = True
        np.not_equal(v_has[1:], v_has[:-1], out=change[1:])
        change[1:] |= (v_mask[1:] != v_mask[:-1]).any(axis=1)
        starts = np.flatnonzero(change)
        ends = np.append(starts[1:], vis.size)

        if mark_cache is None:
            mark_cache = {}
        spans: List[Dict[str, Any]] = []
        for s, e in zip(starts, ends):
            if v_has[s]:
                # Mask bits index this replica's own mark table, so a shared
                # cache must key on the (deduped) table identity too.
                key = (id(table), v_mask[s].tobytes())
                marks = mark_cache.get(key)
                if marks is None:
                    marks = ops_to_marks(decode_row(v_mask[s]), table)
                    mark_cache[key] = marks
            else:
                marks = {}
            text = self._codepoints_to_str(v_chars[s:e])
            if spans and spans[-1]["marks"] == marks:
                spans[-1]["text"] += text  # the coalescing rule
            else:
                spans.append({"marks": dict(marks), "text": text})
        return spans

    def _text_source(self, r: int) -> Optional[str]:
        """Which list object ``root.text`` currently resolves to.

        Returns None when that is the device-bound list (the overwhelmingly
        common case) or the winning object id when map-key LWW
        (micromerge.ts:578-602) elected a *different* root "text" list than
        the one the device plane bound to.  The device binding is permanent
        and first-wins per replica, so with concurrent genesis makeLists two
        replicas can bind different lists — both still hold every list's
        content (ops route by object id; the non-bound list lives in the
        host store), and every view resolves through LWW, so they converge
        exactly like the oracle.  Note the *digest* compares device states
        only and can false-alarm in this adversarial double-genesis case.
        """
        winner = self.stores[r].metadata[None].children.get("text")
        if winner is None or winner == self.text_objs[r]:
            return None
        return winner

    def spans(self, replica: str | int) -> List[Dict[str, Any]]:
        """Materialize one replica as formatted spans (the batch codepath).

        Boundary resolution happens on device (flatten_sources); bitset
        decoding and opsToMarks run on host over the (deduped) distinct mask
        rows, sharing the oracle's resolution code so both engines agree by
        construction.
        """
        r = replica if isinstance(replica, int) else self.index_of[replica]
        host = self._text_source(r)
        if host is not None:
            store = self.stores[r]
            return oracle_spans(
                store.objects[host], store.metadata[host], store.mark_ops
            )
        state = index_state(self.states, r)
        mask, has = K.flatten_sources_jit(state)
        n = int(state.length)
        return self._spans_from_arrays(
            np.asarray(mask[:n]),
            np.asarray(has[:n]),
            np.asarray(state.deleted[:n]),
            np.asarray(state.chars[:n]),
            self._mark_op_table(state),
        )

    def spans_batch(self) -> List[List[Dict[str, Any]]]:
        """All replicas' formatted spans from one batched device launch.

        The flatten runs batched on device; host decode is numpy-segmented
        per replica with the mark table and resolution cache shared across
        the batch (converged replicas share every distinct bitset row).
        """
        mask, has = K.flatten_sources_batch(self.states)
        mask_np = np.asarray(mask)
        has_np = np.asarray(has)
        deleted = np.asarray(self.states.deleted)
        chars = np.asarray(self.states.chars)
        lengths = np.asarray(self.states.length)
        table = self._batch_mark_op_table()
        mark_cache: Dict[Any, Dict[str, Any]] = {}
        out = []
        for r in range(len(self.replica_ids)):
            host = self._text_source(r)
            if host is not None:
                store = self.stores[r]
                out.append(
                    oracle_spans(
                        store.objects[host], store.metadata[host], store.mark_ops
                    )
                )
                continue
            n = int(lengths[r])
            out.append(
                self._spans_from_arrays(
                    mask_np[r, :n],
                    has_np[r, :n],
                    deleted[r, :n],
                    chars[r, :n],
                    table[r],
                    mark_cache,
                )
            )
        return out

    def text(self, replica: str | int) -> str:
        r = replica if isinstance(replica, int) else self.index_of[replica]
        host = self._text_source(r)
        if host is not None:
            return "".join(self.stores[r].objects[host])
        state = index_state(self.states, r)
        n = int(state.length)
        chars = np.asarray(state.chars[:n])
        deleted = np.asarray(state.deleted[:n])
        return self._codepoints_to_str(chars[~deleted])

    def texts(self) -> List[str]:
        """All replicas' visible texts from one batched device readback."""
        chars = np.asarray(self.states.chars)
        deleted = np.asarray(self.states.deleted)
        lengths = np.asarray(self.states.length)
        out = []
        for r in range(len(self.replica_ids)):
            host = self._text_source(r)
            if host is not None:
                out.append("".join(self.stores[r].objects[host]))
                continue
            n = int(lengths[r])
            row = chars[r, :n]
            out.append(self._codepoints_to_str(row[~deleted[r, :n]]))
        return out

    def digests(self) -> np.ndarray:
        """Per-replica convergence digests in one batched device call."""
        ranks, multi = self._put(
            (self._ranks(), allow_multiple_array()), replicated=True
        )
        return np.asarray(K.convergence_digest_batch(self.states, ranks, multi))

    def get_cursor(self, replica: str | int, index: int) -> Dict[str, Any]:
        """Stable cursor for a visible index (reference micromerge.ts:465-472)."""
        r = replica if isinstance(replica, int) else self.index_of[replica]
        host = self._text_source(r)
        if host is not None:
            return {
                "objectId": host,
                "elemId": get_list_element_id(self.stores[r].metadata[host], index),
            }
        state = index_state(self.states, r)
        ctr, act, found = K.cursor_elem_jit(state, jax.numpy.int32(index))
        if not bool(found):
            raise IndexError(f"List index out of bounds: {index}")
        return {
            "objectId": self.text_objs[r],
            "elemId": make_op_id(int(ctr), self.actors.actor(int(act))),
        }

    def resolve_cursor(self, replica: str | int, cursor: Dict[str, Any]) -> int:
        """Current visible index of a cursor (reference micromerge.ts:475-477)."""
        from peritext_tpu.ids import parse_op_id

        r = replica if isinstance(replica, int) else self.index_of[replica]
        obj = cursor.get("objectId")
        if obj is not None and obj != self.text_objs[r]:
            # Cursor into a host-side list (e.g. the LWW-winning text list
            # when the device bound a different one).
            _, visible = self.stores[r].find_list_element(obj, cursor["elemId"])
            return visible
        state = index_state(self.states, r)
        ctr, actor = parse_op_id(cursor["elemId"])
        if actor not in self.actors:
            raise KeyError(f"List element not found: {cursor['elemId']}")
        act = self.actors.id_of(actor)
        index, found = K.resolve_cursor_index_jit(
            state, jax.numpy.int32(ctr), jax.numpy.int32(act)
        )
        if not bool(found):
            raise KeyError(f"List element not found: {cursor['elemId']}")
        return int(index)

    def get_cursors(self, indices: Sequence[int]) -> List[Dict[str, Any]]:
        """Stable cursors for one visible index per replica, in one launch
        (the fleet form of get_cursor)."""
        if len(indices) != len(self.replica_ids):
            raise ValueError("need one index per replica")
        if any(self._text_source(r) is not None for r in range(len(indices))):
            # Adversarial double-genesis fleet: some replicas' text resolves
            # host-side; take the per-replica path.
            return [self.get_cursor(r, i) for r, i in enumerate(indices)]
        ctrs, acts, founds = K.cursor_elems_batch(
            self.states, jax.numpy.asarray(np.asarray(indices, np.int32))
        )
        founds = np.asarray(founds)
        if not founds.all():
            bad = int(np.flatnonzero(~founds)[0])
            raise IndexError(f"List index out of bounds: {indices[bad]} (replica {bad})")
        ctrs = np.asarray(ctrs)
        acts = np.asarray(acts)
        return [
            {
                "objectId": self.text_objs[r],
                "elemId": make_op_id(int(ctrs[r]), self.actors.actor(int(acts[r]))),
            }
            for r in range(len(self.replica_ids))
        ]

    def resolve_cursors(self, cursors: Sequence[Dict[str, Any]]) -> List[int]:
        """Current visible indices of one cursor per replica, in one launch."""
        from peritext_tpu.ids import parse_op_id

        if len(cursors) != len(self.replica_ids):
            raise ValueError("need one cursor per replica")
        if any(
            c.get("objectId") is not None and c.get("objectId") != self.text_objs[r]
            for r, c in enumerate(cursors)
        ):
            return [self.resolve_cursor(r, c) for r, c in enumerate(cursors)]
        ctrs = np.zeros(len(cursors), np.int32)
        acts = np.zeros(len(cursors), np.int32)
        for r, cursor in enumerate(cursors):
            ctr, actor = parse_op_id(cursor["elemId"])
            if actor not in self.actors:
                raise KeyError(f"List element not found: {cursor['elemId']}")
            ctrs[r] = ctr
            acts[r] = self.actors.id_of(actor)
        idxs, founds = K.resolve_cursor_indices_batch(
            self.states, *self._put((ctrs, acts))
        )
        founds = np.asarray(founds)
        if not founds.all():
            bad = int(np.flatnonzero(~founds)[0])
            raise KeyError(f"List element not found: {cursors[bad]['elemId']}")
        return [int(i) for i in np.asarray(idxs)]

    def clock(self, replica: str | int) -> Dict[str, int]:
        r = replica if isinstance(replica, int) else self.index_of[replica]
        return dict(self.clocks[r])
