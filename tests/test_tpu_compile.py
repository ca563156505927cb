"""Main-path programs compile for a described TPU v5e (no chip needed).

The TPU compiler ships with the installed libtpu and compiles for a
topology that is described, not attached, so these catch what CPU runs
cannot: Mosaic lowering errors in the Pallas kernels, TPU-only lowering
breaks in the merge programs, and programs that do not fit the chip's
16 GB.  Shapes are captured from tiny CPU runs of the real entry points
and widened to the real replica counts; nothing runs, so nothing here
says anything about results or times.

The topology is described inside a module fixture (never at import): only
one process may load libtpu, and the test workers each import this file.
"""
import functools
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from peritext_tpu.bench.workloads import build_device_batch, make_merge_workload
from peritext_tpu.ops import kernels as K

V5E_HBM_BYTES = 16 * 2**30
SMALL_R = 24  # replica width of the captured CPU runs (no other axis is 24)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("replica", "seq"))


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _capture(monkeypatch, name, drive):
    """Run ``drive()`` on the CPU and return the (args, kwargs) of every
    call it makes to ``kernels.<name>``."""
    calls = []
    orig = getattr(K, name)

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return orig(*args, **kwargs)

    monkeypatch.setattr(K, name, spy)
    drive()
    assert calls, f"the drive never reached kernels.{name}"
    return orig, calls


def _compile(fn, args, kwargs, width, rows, whole=None):
    """Compile ``fn(*args, **kwargs)`` with every array argument turned into
    a shape: a leading axis of SMALL_R replicas widens to ``width`` and is
    placed on ``rows``; other arrays (rank and flag tables) on ``whole``
    (default ``rows``).  Returns the compiled program."""
    leaves, tree = jax.tree.flatten((args, kwargs))
    arrays = [i for i, x in enumerate(leaves) if isinstance(x, (jax.Array, np.ndarray))]

    def shape_of(x):
        if x.ndim and x.shape[0] == SMALL_R:
            return jax.ShapeDtypeStruct((width,) + x.shape[1:], x.dtype, sharding=rows)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole or rows)

    def call(*xs):
        full = list(leaves)
        for i, x in zip(arrays, xs):
            full[i] = x
        a, kw = jax.tree.unflatten(tree, full)
        return fn(*a, **kw)

    return jax.jit(call).lower(*[shape_of(leaves[i]) for i in arrays]).compile()


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    need = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    assert need < V5E_HBM_BYTES, f"{need / 2**30:.1f} GiB does not fit one v5e"


def _fleet_calls(monkeypatch, name):
    """The merges chip_smoke's fleet phase makes (genesis, then a windowed
    round), at SMALL_R."""
    import chip_smoke as smoke

    traffic = smoke.fleet_traffic(rounds=1, round_ops=16)
    return _capture(
        monkeypatch,
        name,
        lambda: smoke.run_fleet(SMALL_R, traffic, smoke.CompileClock(), max_mark_ops=256),
    )


def test_sorted_merge_compiles_at_bench_shape(one_chip):
    """The default merge step as bench.py runs it: R=1024 1k-char docs at
    capacity 2048 with a 1024-op mark table, 64-op concurrent batches."""
    from peritext_tpu.ops.encode import prepare_sorted_batch

    batch = build_device_batch(make_merge_workload(1000, 64, 4, True, 0), SMALL_R, 2048, 1024)
    sp = prepare_sorted_batch([batch["text_ops"][r] for r in range(SMALL_R)])
    args = (batch["states"], sp["text"], sp["rounds"], sp["num_rounds"],
            batch["mark_ops"], batch["ranks"], sp["bufs"], sp["maxk"])
    _fits_one_chip(_compile(K.merge_step_sorted_batch, args, {}, 1024, one_chip))


def test_full_table_merge_has_no_full_plane_gather(one_chip):
    """The benchmark fleet's full-table merge: 5,120 1k-char docs at
    capacity 2048 with a 256-op mark table, two 64-op concurrent streams
    with 8 marks each per replica.  XLA on the TPU runs a gather with
    per-element indices close to one element at a time, so the merge moves
    and looks up per-slot data by shift and select passes: no gather in the
    optimised program may produce R * C elements or more."""
    import re

    from peritext_tpu.ops.state import make_empty_state, stack_states

    replicas, capacity = 5120, 2048
    states = stack_states([make_empty_state(capacity, 256)] * SMALL_R)
    args = (
        states,
        np.zeros((SMALL_R, 128, K.OP_FIELDS), np.int32),  # text rows
        np.zeros((SMALL_R, 128), np.int32),  # placement round of each row
        np.array(1, np.int32),  # num_rounds: a runtime scalar, as launched
        np.zeros((SMALL_R, 16, K.OP_FIELDS), np.int32),  # mark rows
        np.zeros(64, np.int32),  # actor ranks
        np.zeros((SMALL_R, 128), np.int32),  # run chars
        4,  # maxk
    )
    compiled = _compile(K.merge_step_sorted_batch, args, {}, replicas, one_chip)
    _fits_one_chip(compiled)
    big = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* gather\(", line)
        if m and np.prod([int(d) for d in m.group(2).split(",") if d]) >= replicas * capacity:
            big.append(m.group(1))
    assert not big, f"full-plane gathers in the merge: {big}"


def test_smoke_fleet_fits_one_chip(one_chip, monkeypatch):
    """chip_smoke's fleet width on one chip: the genesis merge (the
    phase's largest program) must fit the chip's HBM."""
    import chip_smoke as smoke

    orig, calls = _fleet_calls(monkeypatch, "merge_step_sorted_batch")
    args, kwargs = calls[0]
    _fits_one_chip(_compile(orig, args, kwargs, smoke.FLEET_REPLICAS, one_chip))


@pytest.mark.parametrize(
    "merge", ["merge_step_sorted_batch", "merge_step_sorted_windowed_batch"]
)
def test_mesh_fleet_merge_compiles_for_four_chips(mesh4, monkeypatch, merge):
    """chip_smoke --chips 4's mesh fleet: its genesis and windowed merges
    partitioned over the replica axis of a 2x2 v5e, each chip within its
    HBM."""
    import chip_smoke as smoke

    orig, calls = _fleet_calls(monkeypatch, merge)
    args, kwargs = calls[0]
    compiled = _compile(
        orig, args, kwargs, smoke.FLEET_REPLICAS,
        NamedSharding(mesh4, P("replica")), NamedSharding(mesh4, P()),
    )
    _fits_one_chip(compiled)  # memory_analysis counts one chip's share


def test_windowed_merge_compiles_at_w_cap_512(one_chip, monkeypatch):
    orig, calls = _fleet_calls(monkeypatch, "merge_step_sorted_windowed_batch")
    args, kwargs = calls[0]
    args = args[:-1] + (512,)  # w_cap, static
    _fits_one_chip(_compile(orig, args, kwargs, 1024, one_chip))


def test_patched_delta_readback_compiles_at_serving_shape(one_chip, monkeypatch):
    """The serving plane's patch-emitting merge (compact-delta records read
    back per cohort) at chip_smoke's 1,024 sessions of 1k-char docs."""
    import chip_smoke as smoke

    traffic = smoke.serve_traffic(SMALL_R)

    def drive():
        plane = smoke.serve_plane(SMALL_R)
        try:
            smoke.run_serving(plane, traffic, smoke.CompileClock())
        finally:
            plane.close()

    orig, calls = _capture(monkeypatch, "merge_step_sorted_patched_windowed_batch", drive)
    marked = [c for c in calls if c[1].get("has_marks")]
    args, kwargs = marked[-1]
    assert kwargs["mode"] == "delta" and kwargs["readback"] == "compact"
    _fits_one_chip(
        _compile(orig, args, kwargs, smoke.SERVE_SESSIONS, one_chip)
    )


@pytest.mark.parametrize("kernel", ["text", "mark"])
def test_pallas_kernel_compiles_at_capacity_2048(one_chip, kernel):
    from peritext_tpu.ops import pallas_kernels as PK

    batch = build_device_batch(
        make_merge_workload(1000, 64, 4, True, 0), SMALL_R, 2048, 256
    )
    st = batch["states"]
    if kernel == "text":
        fn = functools.partial(PK.text_phase_pallas, interpret=False)
        args = (st.elem_ctr, st.elem_act, st.deleted, st.chars, st.length,
                batch["text_ops"], batch["ranks"])
        kwargs = {"char_buf": np.zeros((SMALL_R, 2048), np.int32)}
    else:
        fn = functools.partial(PK.mark_phase_pallas, interpret=False)
        args = (st.bnd_def, st.bnd_mask, st.elem_ctr, st.elem_act, st.length,
                st.mark_count, batch["mark_ops"])
        kwargs = {}
    compiled = _compile(fn, args, kwargs, SMALL_R, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_window_reach_bound_stays_fused_at_fleet_shape(one_chip):
    """The census's reach bound over the benchmark fleet's table (5,120
    replicas at capacity 2048, ``REACH_IDS`` ids each): the per-id compare
    over the table stays inside the reduction's fusion, so the program's
    scratch holds no [R, ids, C] plane, nor even one [R, C] one."""
    from peritext_tpu.ops import window as W

    replicas, capacity = 5120, 2048
    args = (
        np.zeros((SMALL_R, capacity), np.int32),  # elem_ctr
        np.zeros((SMALL_R, capacity), np.int32),  # elem_act
        np.zeros(SMALL_R, np.int32),  # length
        np.zeros((SMALL_R, W.REACH_IDS, 2), np.int32),  # ids
        np.zeros(SMALL_R, bool),  # head
        np.zeros(SMALL_R, np.int32),  # inserts
    )
    compiled = _compile(K.window_reach_bound, args, {}, replicas, one_chip)
    _fits_one_chip(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < replicas * capacity * 4
    assert "merge_step_sorted" not in compiled.as_text()
