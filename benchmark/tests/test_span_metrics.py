"""The readers of the program's host-phase spans, and the idle-gap label
those spans give in a reduced trace."""
import os

import pytest

import run
import trace_reduce as TR

SPANS = {
    "ingest.prepare_ms.fleet": "ingest.prepare",
    "ingest.stage_ms.fleet": "ingest.stage",
    "ingest.census_ms.fleet": "ingest.window_census",
    "ingest.put_ms.fleet": "ingest.put",
}


def reader(metric):
    return run.load_module(os.path.join(run.HERE, "metrics", f"{metric}.py"),
                           "metric_t_" + metric.replace(".", "_"))


def obs(span, before, after, rounds):
    def snap(count_sum):
        hists = {} if count_sum is None else {
            f"span.{span}.seconds": {"count": count_sum[0], "sum": count_sum[1]}}
        return {"histograms": hists}

    return {"telemetry": {"before": snap(before), "after": snap(after)},
            "units": {"rounds": rounds}}


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_reader_gives_ms_per_round(metric):
    read = reader(metric).read
    span = SPANS[metric]
    # 5 spans totalling 1.0 s over the window, 4 rounds: 250 ms a round.
    assert read(obs(span, (3, 0.5), (8, 1.5), 4)) == pytest.approx(250.0)
    assert read(obs(span, None, (2, 0.2), 2)) == pytest.approx(100.0)


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_reader_none_without_span_or_rounds(metric):
    read = reader(metric).read
    span = SPANS[metric]
    assert read(obs(span, None, None, 4)) is None
    assert read(obs("other.span", (0, 0.0), (4, 1.0), 4)) is None
    assert read(obs(span, (0, 0.0), (4, 1.0), 0)) is None
    assert read({"telemetry": {}, "units": {"rounds": 4}}) is None


def test_census_span_labels_the_gap_before_a_launch():
    """A device gap between two rounds' launches that the census span
    covers, with shorter readbacks inside it, takes the census's name."""
    ms = 1_000_000
    device = {"ops": [("merge", 0, 100 * ms), ("merge", 400 * ms, 500 * ms)],
              "modules": [("jit_merge_step_sorted(1)", 0, 100 * ms),
                          ("jit_merge_step_sorted(1)", 400 * ms, 500 * ms)]}
    host = [("bench.round", 0, 101 * ms), ("bench.round", 102 * ms, 500 * ms),
            ("ingest.prepare", 103 * ms, 130 * ms),
            ("ingest.stage", 130 * ms, 140 * ms),
            ("ingest.window_census", 140 * ms, 390 * ms),
            ("np.asarray_jax.Array_", 141 * ms, 200 * ms),
            ("np.asarray_jax.Array_", 201 * ms, 260 * ms),
            ("XlaLinearize", 261 * ms, 300 * ms),
            ("ingest.put", 390 * ms, 398 * ms)]
    red = TR.reduce_events([device], host, "bench.round")
    name, seconds = red["idle_gaps"][0]
    assert name == "ingest.window_census"
    assert seconds == pytest.approx(0.298)  # from the second round's start, 102 ms, to 400 ms
