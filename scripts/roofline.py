#!/usr/bin/env python3
"""Analytic roofline for the merge paths, from the TPU compiler's cost model.

Compiles the production kernels with the image's local libtpu against an
abstract v5e topology — the *real* XLA:TPU compiler, no hardware — and pulls
the compiler's own cost model (`compiled.cost_analysis()`: flops, HBM bytes
accessed, optimal_seconds) for:

  - the headline bench shape (R=1024 replicas, 1k-char docs, 64-op merges),
  - the per-phase attribution (text placement vs mark phase),
  - the latency shape (R=1, 10k-char doc),
  - the patch-emitting sorted merge.

From bytes/flops and v5e-1 peaks (819 GB/s HBM, 197 bf16 TFLOPs MXU, ~4 T
int-op/s VPU) it derives the bandwidth-bound and compute-bound ceilings in
ops/s and compares the last hardware self-measurement against them.

Usage:
    python scripts/roofline.py            # all targets, JSON per line
    python scripts/roofline.py --budget   # HBM budget table (config 5 math)
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# v5e-1 peaks (public: cloud.google.com/tpu/docs/v5e, scaling-book ch.2).
HBM_GBPS = 819e9
MXU_BF16_FLOPS = 197e12
# VPU elementwise lane throughput: (8,128) vregs x 4 ALUs x ~940 MHz.
VPU_OPS = 3.8e12
HBM_BYTES = 16 * 2**30


def _jax():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def budget() -> None:
    """DocState HBM bytes/replica as f(C, M) and max replicas per v5e chip.

    Config 5 (BASELINE.json): 100k replicas x 10k-char docs. A 10k-char doc
    needs capacity C=16384; the table answers whether the shape fits.
    """
    jax = _jax()
    from peritext_tpu.ops.state import make_empty_state

    rows = []
    for c, m in [(2048, 1024), (4096, 1024), (16384, 1024), (16384, 4096)]:
        st = make_empty_state(c, m)
        per = sum(np.asarray(x).nbytes for x in jax.tree.leaves(st))
        fit1 = int(HBM_BYTES * 0.9 // per)  # 10% headroom for transients
        rows.append(
            {
                "capacity": c,
                "max_mark_ops": m,
                "state_bytes_per_replica": per,
                "state_mib_per_replica": round(per / 2**20, 2),
                "max_replicas_v5e_1": fit1,
                "max_replicas_v5e_8": fit1 * 8,
            }
        )
        print(json.dumps(rows[-1]))
    return rows


def _cost(compiled):
    ca = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    # Note: ca['optimal_seconds'] is garbage (negative) from the TPU AOT
    # backend; derive times from bytes/flops and public peaks instead.
    return {
        "flops": ca.get("flops", 0.0),
        "hbm_bytes": ca.get("bytes accessed", 0.0),
        "temp_mib": round(getattr(mem, "temp_size_in_bytes", 0) / 2**20, 1),
    }


def _ceilings(cost, ops_per_launch):
    t_bw = cost["hbm_bytes"] / HBM_GBPS
    t_vpu = cost["flops"] / VPU_OPS  # merge flops are VPU int/bool, not MXU
    t = max(t_bw, t_vpu)
    return {
        "t_bandwidth_ms": round(t_bw * 1e3, 3),
        "t_vpu_ms": round(t_vpu * 1e3, 3),
        "bound": "bandwidth" if t_bw >= t_vpu else "compute",
        "ceiling_ops_per_sec": round(ops_per_launch / t, 1) if t else None,
    }


def main() -> int:
    if "--budget" in sys.argv:
        budget()
        return 0

    jax = _jax()
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from peritext_tpu.bench.workloads import build_device_batch, make_merge_workload
    from peritext_tpu.ops import kernels as K
    from peritext_tpu.ops.encode import prepare_sorted_batch

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2x1")
    n_dev = len(topo.devices)
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("x",))
    row = NamedSharding(mesh, P("x"))
    repl = NamedSharding(mesh, P())

    def sds(x, sh):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    # --- headline bench shape -------------------------------------------
    R, doc_len, ops_per_merge, rounds = 1024, 1000, 64, 8
    workload = make_merge_workload(doc_len, ops_per_merge, 4, True, 0)
    capacity = 1
    while capacity < doc_len + (rounds + 1) * ops_per_merge + 8:
        capacity *= 2
    batch = build_device_batch(workload, R, capacity, 1024)
    sp = prepare_sorted_batch(
        [batch["text_ops"][r] for r in range(R)], max_run=0
    )
    ops_total = batch["total_ops"]  # ops per merge launch over all R
    per_chip_ops = ops_total / n_dev

    st_sds = jax.tree.map(lambda x: sds(x, row), batch["states"])
    text = sds(sp["text"], row)
    marks = sds(batch["mark_ops"], row)
    ranks = sds(batch["ranks"], repl)
    bufs = sds(sp["bufs"], row)
    rounds_sds = sds(sp["rounds"], row)

    only = None
    if "--only" in sys.argv:
        only = set(sys.argv[sys.argv.index("--only") + 1].split(","))

    def want(key):
        return only is None or key in only

    dump_hlo = "--dump-hlo" in sys.argv

    def report(name, compiled, ops_per_launch, extra=None):
        cost = _cost(compiled)
        out = {
            "target": name,
            **(extra or {}),
            **cost,
            **_ceilings(cost, ops_per_launch),
        }
        print(json.dumps(out), flush=True)
        if dump_hlo:
            slug = "".join(ch if ch.isalnum() else "_" for ch in name)
            with open(f"/tmp/hlo_{slug}.txt", "w") as f:
                f.write(compiled.as_text())
        return out

    shape_info = {
        "R_per_chip": R // n_dev,
        "capacity": capacity,
        "num_rounds": sp["num_rounds"],
        "maxk": sp["maxk"],
        "ops_per_launch_per_chip": per_chip_ops,
    }

    # Dynamic-rounds variant (what the bench actually launches: num_rounds
    # is a traced scalar -> XLA while loop, whose cost model guesses a trip
    # count) and static-rounds variant (trip count baked in = the work the
    # hardware actually executes at this shape).  The static one is the
    # honest roofline; the delta is cost-model inflation, not real traffic.
    import functools

    if want("dynamic"):
        full = jax.jit(
            lambda st, t, ro, m, rk, b: K.merge_step_sorted_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, sp["maxk"]
            )
        ).lower(st_sds, text, rounds_sds, marks, ranks, bufs).compile()
        report("merge_step_sorted @bench (dynamic rounds)", full, per_chip_ops, shape_info)

    if want("static"):
        full_static = jax.jit(
            lambda st, t, ro, m, rk, b: jax.vmap(
                functools.partial(K.merge_step_sorted, maxk=sp["maxk"]),
                in_axes=(0, 0, 0, None, 0, None, 0),
            )(st, t, ro, jnp.int32(sp["num_rounds"]), m, rk, b)
        ).lower(st_sds, text, rounds_sds, marks, ranks, bufs).compile()
        report("merge_step_sorted @bench (static rounds)", full_static, per_chip_ops, shape_info)

    # --- phase attribution ----------------------------------------------
    if want("text"):
        text_only = jax.jit(
            lambda st, t, ro, rk, b: jax.vmap(
                lambda s, tt, rro, bb: K.place_text_batch(
                    s.elem_ctr, s.elem_act, s.deleted, s.chars, s.length,
                    tt, rro, jnp.int32(sp["num_rounds"]), rk, bb, sp["maxk"],
                ),
                in_axes=(0, 0, 0, 0),
            )(st, t, ro, b)
        ).lower(st_sds, text, rounds_sds, ranks, bufs).compile()
        report("place_text_batch @bench", text_only, per_chip_ops)

    if want("tail"):
        def tail_fn(st, m, rk):
            def one(s, mm):
                return K._sorted_tail(
                    s, s.elem_ctr, s.elem_act, s.deleted, s.chars, s.length,
                    s.bnd_def, s.bnd_mask, mm,
                )

            return jax.vmap(one)(st, m)

        tail = jax.jit(tail_fn).lower(st_sds, marks, ranks).compile()
        report("mark_phase(_sorted_tail) @bench", tail, per_chip_ops)

    # --- patched path ----------------------------------------------------
    # "patched"/"patched_threaded" pin mode="dense" (the r4/r5-comparable
    # full-plane scan); "patched_delta"/"patched_delta_threaded" score the
    # compact-delta scan that replaced it as the default.
    if want("patched"):
        from peritext_tpu.schema import allow_multiple_array

        multi = sds(allow_multiple_array(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        patched = jax.jit(
            lambda st, t, ro, m, rk, b, mu, tp, mp: K.merge_step_sorted_patched_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, mu, tp, mp, sp["maxk"],
                mode="dense",
            )
        ).lower(st_sds, text, rounds_sds, marks, ranks, bufs, multi, tpos, mpos).compile()
        report("merge_step_sorted_patched @bench (dense)", patched, per_chip_ops)

    if want("patched_delta"):
        from peritext_tpu.schema import allow_multiple_array

        multi = sds(allow_multiple_array(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        # group_k=4: the host census sizes the delta scan's allowMultiple
        # resolution per batch; this workload's comment groups are 1-2 ops
        # (distinct random ids), so 4 is the realistic compiled width (the
        # dense targets always pay the full PATCH_GROUP_K machinery).
        patched_d = jax.jit(
            lambda st, t, ro, m, rk, b, mu, tp, mp: K.merge_step_sorted_patched_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, mu, tp, mp, sp["maxk"],
                mode="delta", group_k=4, t_act=4,
            )
        ).lower(st_sds, text, rounds_sds, marks, ranks, bufs, multi, tpos, mpos).compile()
        report(
            "merge_step_sorted_patched @bench (compact-delta)",
            patched_d,
            per_chip_ops,
            {"group_k": 4, "t_act": 4},
        )

    if want("windowed"):
        # Frontier-bounded window merge (ISSUE 12): the same patched-delta
        # program gathered over [R, w_cap] windows — the target whose HLO
        # output-sum should scale with w_cap, not capacity, apart from the
        # one gather/scatter pass over the full planes.
        from peritext_tpu.schema import allow_multiple_array

        multi = sds(allow_multiple_array(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        w_cap = 256
        iv = sds(np.zeros(R, np.int32), row)
        windowed = jax.jit(
            lambda st, s, h, vb, va, t, ro, m, rk, b, mu, tp, mp: (
                K.merge_step_sorted_patched_windowed_batch(
                    st, s, h, vb, va, t, ro, sp["num_rounds"], m, rk, b, mu,
                    tp, mp, sp["maxk"], w_cap, mode="delta", group_k=4,
                    t_act=4,
                )
            )
        ).lower(
            st_sds, iv, iv, iv, iv, text, rounds_sds, marks, ranks, bufs,
            multi, tpos, mpos,
        ).compile()
        report(
            "merge_step_sorted_patched_windowed @bench (w_cap=256)",
            windowed,
            per_chip_ops,
            {"w_cap": w_cap},
        )

    if want("patched_nomarks"):
        from peritext_tpu.schema import allow_multiple_array

        multi = sds(allow_multiple_array(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        patched_nm = jax.jit(
            lambda st, t, ro, m, rk, b, mu, tp, mp: K.merge_step_sorted_patched_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, mu, tp, mp, sp["maxk"],
                has_marks=False,
            )
        ).lower(st_sds, text, rounds_sds, marks, ranks, bufs, multi, tpos, mpos).compile()
        report("merge_step_sorted_patched @bench (no-marks fast path)", patched_nm, per_chip_ops)

    if want("patched_threaded"):
        from peritext_tpu.schema import allow_multiple_array as _ama

        multi = sds(_ama(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        n_types = int(np.asarray(_ama()).shape[0])
        wc = sds(
            np.zeros((R, 2 * capacity, n_types, 4), np.int32), row
        )
        threaded = jax.jit(
            lambda st, t, ro, m, rk, b, mu, tp, mp, w: K.merge_step_sorted_patched_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, mu, tp, mp, sp["maxk"],
                wcache_in=w, mode="dense",
            )
        ).lower(
            st_sds, text, rounds_sds, marks, ranks, bufs, multi, tpos, mpos, wc
        ).compile()
        report(
            "merge_step_sorted_patched @bench (dense, threaded cache, no init)",
            threaded,
            per_chip_ops,
        )

    if want("patched_delta_threaded"):
        from peritext_tpu.schema import allow_multiple_array as _ama

        multi = sds(_ama(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        n_types = int(np.asarray(_ama()).shape[0])
        wc = sds(
            np.zeros((R, 2 * capacity, n_types, 4), np.int32), row
        )
        threaded_d = jax.jit(
            lambda st, t, ro, m, rk, b, mu, tp, mp, w: K.merge_step_sorted_patched_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, mu, tp, mp, sp["maxk"],
                wcache_in=w, mode="delta", group_k=4, t_act=4,
            )
        ).lower(
            st_sds, text, rounds_sds, marks, ranks, bufs, multi, tpos, mpos, wc
        ).compile()
        report(
            "merge_step_sorted_patched @bench (compact-delta, threaded cache)",
            threaded_d,
            per_chip_ops,
            {"group_k": 4, "t_act": 4},
        )

    if want("patched_compact"):
        # The compact-readback variant of the steady-state launch
        # (ISSUE 8): same compact-delta threaded-cache program, but the
        # record outputs are [M, span_cap] run tables instead of the
        # [M, 2C] mark planes — the D2H seam the readback cut targets.
        from peritext_tpu.schema import allow_multiple_array as _ama

        multi = sds(_ama(), repl)
        tpos = sds(np.zeros(sp["text"].shape[:2], np.int32), row)
        mpos = sds(np.zeros(batch["mark_ops"].shape[:2], np.int32), row)
        n_types = int(np.asarray(_ama()).shape[0])
        wc = sds(
            np.zeros((R, 2 * capacity, n_types, 4), np.int32), row
        )
        compact_d = jax.jit(
            lambda st, t, ro, m, rk, b, mu, tp, mp, w: K.merge_step_sorted_patched_batch(
                st, t, ro, sp["num_rounds"], m, rk, b, mu, tp, mp, sp["maxk"],
                wcache_in=w, mode="delta", group_k=4, t_act=4,
                readback="compact",
            )
        ).lower(
            st_sds, text, rounds_sds, marks, ranks, bufs, multi, tpos, mpos, wc
        ).compile()
        report(
            "merge_step_sorted_patched @bench (compact readback, threaded cache)",
            compact_d,
            per_chip_ops,
            {"group_k": 4, "t_act": 4, "readback": "compact"},
        )

    if not want("latency"):
        return 0

    # --- latency shape: R=1, 10k-char doc -------------------------------
    doc_len_l, trials_ops = 10_000, 64
    wl = make_merge_workload(doc_len_l, trials_ops, 4, True, 0)
    cap_l = 1
    while cap_l < doc_len_l + 3 * trials_ops + 8:
        cap_l *= 2
    b1 = build_device_batch(wl, 1, cap_l, 1024)
    sp1 = prepare_sorted_batch([b1["text_ops"][0]], max_run=0)
    one = NamedSharding(Mesh(np.array(topo.devices)[:1].reshape(-1), ("x",)), P())
    st1 = jax.tree.map(lambda x: sds(x, one), b1["states"])
    lat = jax.jit(
        lambda st, t, ro, m, rk, b: jax.vmap(
            functools.partial(K.merge_step_sorted, maxk=sp1["maxk"]),
            in_axes=(0, 0, 0, None, 0, None, 0),
        )(st, t, ro, jnp.int32(sp1["num_rounds"]), m, rk, b)
    ).lower(
        st1,
        sds(sp1["text"], one),
        sds(sp1["rounds"], one),
        sds(b1["mark_ops"], one),
        sds(b1["ranks"], one),
        sds(sp1["bufs"], one),
    ).compile()
    report(
        "merge_step_sorted @latency(R=1,10k)",
        lat,
        b1["total_ops"],
        {"capacity": cap_l, "num_rounds": sp1["num_rounds"], "maxk": sp1["maxk"]},
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
