"""The ``serve-4k.sync`` cell at test size on the CPU: its closed-loop
traffic is a function of the seed that keeps the amount of work and fits
the docs' capacity; its driver runs end to end through ``run.main`` on
four virtual devices and reads correct; with the program taking the
configuration's control shortcut (insert patches without the marks where
the chars land) it reads not correct; the per-layer readers it shares
with the other cells read per shard flush."""
import collections
import json
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

import gen  # noqa: E402
import run  # noqa: E402
from peritext_tpu.ops import TpuUniverse  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 2**31 + 11, 9_876_543_210_123)
CELL = "serve-4k.sync"


def config(**change):
    with open(os.path.join(BENCH, "configs", "serve-4k.json")) as f:
        return dict(json.load(f), **change)


def traffic(seed, docs=24):
    return gen.generate(gen.load_mix("sync"), config(docs=docs), seed, 51)


def scripts(t, upto=None):
    return [[s.get(i) for i in range(upto or len(s))] for s in t["scripts"]]


def test_same_seed_same_script_and_seeds_differ():
    same = [json.dumps([t["geneses"], scripts(t, 40)]) for t in (traffic(SEEDS[1]),
                                                                   traffic(SEEDS[1]))]
    assert same[0] == same[1]
    dumps = {json.dumps(scripts(traffic(s), 40)) for s in SEEDS}
    assert len(dumps) == len(SEEDS)


def test_lazy_and_eager_reads_agree():
    t, u = traffic(SEEDS[0], docs=4), traffic(SEEDS[0], docs=4)
    eager = scripts(t)
    lazy = [[s.get(i) for i in range(len(s))][::-1][::-1] for s in u["scripts"]]
    assert json.dumps(eager) == json.dumps(lazy)


def work(change):
    op = change["ops"][0]
    kind = {"set": "typing", "del": "backspace"}.get(op["action"], "mark")
    return kind, len(change["ops"])


def test_exact_shares_for_every_writer_and_seed():
    mix = gen.load_mix("sync")
    m = mix["changes_per_writer"]
    want = {k: round(v["share"] * m) for k, v in mix["changes"].items()}
    tallies = []
    for seed in SEEDS:
        for script in scripts(traffic(seed, docs=6)):
            assert len(script) == m
            tally = collections.Counter(work(c) for c in script)
            assert {k: sum(n for (kk, _), n in tally.items() if kk == k)
                    for k in want} == want
            tallies.append(tally)
    assert all(t == tallies[0] for t in tallies)


def test_scripts_fit_capacity_far_past_a_run():
    """Tombstones keep their slots, so a script fits its doc's capacity
    only so far: ``fits`` is that count per doc, exactly, and it lies far
    past the ~16 changes a writer sent in a 51 s run on the chip."""
    cfg = config(docs=8)
    t = gen.generate(gen.load_mix("sync"), cfg, SEEDS[2], 51)
    for g, script, fit in zip(t["geneses"], scripts(t), t["fits"]):
        def slots(n):
            return (len(g["ops"]) - 1 - 24) + sum(
                len(c["ops"]) for c in script[:n] if c["ops"][0]["action"] == "set")
        assert slots(fit) <= cfg["capacity"]
        assert fit == len(script) or slots(fit + 1) > cfg["capacity"]
        assert fit >= 300


def tiny(spec):
    spec["config"].update(docs=8, doc_len=60, capacity=256, sampled_docs=8,
                          result_grace_s=30)
    spec["config"]["plane"]["batch_target"] = 2
    spec["mix"]["changes_per_writer"] = 40


def result(capsys):
    assert run.main(["--workload", CELL, "--seed", str(2**33 + 9), "--seconds", "1",
                     "--trace", "0"], require_chip=False, configure=tiny) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def without_marks(orig):
    """The control's shortcut in the program: insert patches leave out the
    marks active where the chars land."""
    def apply(self, per_replica, with_positions=False):
        out = orig(self, per_replica, with_positions=with_positions)
        for pairs in out.values():
            for _, patch in pairs:
                if patch.get("action") == "insert":
                    patch["marks"] = {}
        return out
    return apply


def test_sound_run_is_correct(capsys):
    out = result(capsys)
    assert out["correct"] is True
    assert out["failed"] == 0
    assert out["checks"]["unanswered"]["value"] == 0
    assert out["device"]["count"] == 4


def test_control_reads_not_correct(capsys, monkeypatch):
    method = "apply_changes_with_patches"
    monkeypatch.setattr(TpuUniverse, method, without_marks(getattr(TpuUniverse, method)))
    out = result(capsys)
    assert out["correct"] is False
    assert out["checks"]["submissions_differing"]["value"] > 0


def reader(metric):
    return run.load_module(os.path.join(run.HERE, "metrics", f"{metric}.py"),
                           "metric_t_" + metric.replace(".", "_"))


def snap(hists):
    return {"histograms": {k: {"count": c, "sum": s} for k, (c, s) in hists.items()}}


def test_span_readers_read_per_flush():
    """The ingest phase readers divide by the cell's rounds, which the
    driver sets to its shard flushes."""
    before = snap({"span.serve.flush.seconds": (2, 1.0)})
    after = snap({"span.serve.flush.seconds": (6, 3.0),
                  "span.ingest.prepare.seconds": (4, 0.2),
                  "span.ingest.assemble.seconds": (4, 0.6)})
    obs = {"telemetry": {"before": before, "after": after},
           "units": {"flushes": 4, "rounds": 4}}
    assert reader("serve.flush_ms").read(obs) == pytest.approx(500.0)
    assert reader("ingest.prepare_ms.fleet").read(obs) == pytest.approx(50.0)
    assert reader("ingest.assemble_ms").read(obs) == pytest.approx(150.0)
    assert reader("ingest.put_ms.fleet").read(obs) is None


def test_trace_readers_match_the_patched_merge():
    trace = {"module_s": {"jit_merge_step_sorted_patched_windowed(1)": 0.3,
                          "jit_merge_step_sorted_patched(2)": 0.1,
                          "jit_broadcast_in_dim": 0.5},
             "idle_share": 0.25}
    obs = {"trace": trace, "units": {"flushes": 8, "rounds": 8}}
    assert reader("kernel.merge_ms.fleet").read(obs) == pytest.approx(50.0)
    assert reader("device.idle_share.fleet").read(obs) == 0.25
