"""The readers of the host driver's path traces (``h2d_mb.direct``,
``fetches.direct``, ``driver_share.direct``) on hand-made traces and
answers: each window answer is matched to the trace inside its
``[t0, t1]``, warm-pass traces are ignored, and an answer with no trace
makes every reader give None."""

import pytest

import harness
from repro.obs import trace as obs_trace
from repro.obs.trace import Trace

READERS = ("h2d_mb.direct", "fetches.direct", "driver_share.direct")


def path_trace(t0, phases):
    """A path trace from ``(name, seconds, h2d_bytes, fetches)`` phases."""
    tr = Trace(t0=t0)
    t = t0
    for name, secs, h2d, fetches in phases:
        t += secs
        tr.mark(name, t, step=0, refit=0, h2d_bytes=h2d, fetches=fetches,
                d2h_bytes=4 * fetches)
    return tr


def answer(tr, slack=0.01):
    return harness.Answer(0, tr.t0 - slack, tr.cursor + slack)


@pytest.fixture
def traces(monkeypatch):
    """Two window paths after a warm-pass path; the recorder holds all."""
    warm = path_trace(0.0, [("setup", 1.0, 9_000_000, 9),
                            ("solve", 1.0, 0, 9)])
    a = path_trace(10.0, [("setup", 1.0, 3_000_000, 2),
                          ("solve", 3.0, 1_000_000, 2)])
    b = path_trace(20.0, [("setup", 2.0, 1_000_000, 4),
                          ("solve", 2.0, 1_000_000, 4),
                          ("loss", 1.0, 2_000_000, 2)])
    kept = [warm, a, b]
    monkeypatch.setattr(obs_trace, "recent", lambda: list(kept))
    return kept


@pytest.mark.parametrize("name,want", [
    ("h2d_mb.direct", (4.0 + 4.0) / 2),
    ("fetches.direct", (4 + 10) / 2),
    ("driver_share.direct", (100 * 1 / 4 + 100 * 3 / 5) / 2),
])
def test_reader_reads_the_window_s_paths(traces, name, want):
    _, a, b = traces
    run = dict(answers=[answer(a), answer(b)])
    assert harness.load_reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_for_an_answer_without_a_trace(traces, name):
    _, a, b = traces
    lost = harness.Answer(0, 40.0, 45.0)  # no trace lies in its span
    run = dict(answers=[answer(a), lost, answer(b)])
    assert harness.load_reader(name)(run) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_ignores_warm_pass_traces(traces, name):
    warm, a, b = traces
    with_warm = harness.load_reader(name)(
        dict(answers=[answer(a), answer(b)]))
    traces.remove(warm)
    without = harness.load_reader(name)(dict(answers=[answer(a), answer(b)]))
    assert with_warm == without


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_the_recorder(monkeypatch, traces, name):
    # a program that keeps no path traces (its obs.trace has no recent)
    monkeypatch.delattr(obs_trace, "recent")
    _, a, _ = traces
    assert harness.load_reader(name)(dict(answers=[answer(a)])) is None
