"""The reader of ``prox_live_share.direct`` on hand-made path traces:
100 × Σ ``prox_live`` / Σ ``prox_full`` over the window's ``solve``
spans, and None on traces whose spans carry no such count (a program
whose FISTA does not count its prox's live bound)."""

import pytest

import harness
from repro.obs import trace as obs_trace
from repro.obs.trace import Trace

READER = "prox_live_share.direct"


def path_trace(t0, solves):
    """A path trace of a set-up span, then one ``solve`` span per
    ``(prox_live, prox_full)`` pair (None: the span has neither)."""
    tr = Trace(t0=t0)
    tr.mark("setup", t0 + 1.0, step=0, refit=0, h2d_bytes=0, fetches=1)
    for i, counts in enumerate(solves):
        attrs = dict(step=1 + i, refit=0, h2d_bytes=0, fetches=2)
        if counts is not None:
            attrs.update(prox_live=counts[0], prox_full=counts[1])
        tr.mark("solve", tr.cursor + 1.0, **attrs)
    return tr


def answer(tr, slack=0.01):
    return harness.Answer(0, tr.t0 - slack, tr.cursor + slack)


def read(monkeypatch, kept, window):
    monkeypatch.setattr(obs_trace, "recent", lambda: list(kept))
    return harness.load_reader(READER)(
        dict(answers=[answer(t) for t in window]))


def test_reader_pools_the_window_s_solve_spans(monkeypatch):
    warm = path_trace(0.0, [(1, 1)])
    a = path_trace(10.0, [(100, 1000), (50, 1000)])
    b = path_trace(20.0, [(250, 2000)])
    got = read(monkeypatch, [warm, a, b], [a, b])
    # pooled over every call, not a mean of per-path shares
    assert got == pytest.approx(100.0 * 400 / 4000)


@pytest.mark.parametrize("solves", [[None, None], [(0, 0)], []])
def test_reader_gives_none_without_counts(monkeypatch, solves):
    tr = path_trace(10.0, solves)
    assert read(monkeypatch, [tr], [tr]) is None


def test_reader_gives_none_for_an_answer_without_a_trace(monkeypatch):
    a = path_trace(10.0, [(100, 1000)])
    monkeypatch.setattr(obs_trace, "recent", lambda: [a])
    lost = harness.Answer(0, 40.0, 45.0)
    assert harness.load_reader(READER)(
        dict(answers=[answer(a), lost])) is None
