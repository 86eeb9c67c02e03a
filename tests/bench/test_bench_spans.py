"""The readers of the program's own host spans, on hand-built traces."""

import pytest

from bench import harness, loadgen, program_spans
from bench.trace_reduce import Event, Trace

READERS = [("wave_wait_ms", "mwem/batch/wait"),
           ("final_error_ms", "mwem/batch/final_error"),
           ("deliver_ms", "serve/wave/mwem/deliver")]


def _ctx(host):
    tr = Trace(window=(10.0, 20.0), host=sorted(host, key=lambda e: e.start))
    return harness.Context(cfg={"T": 2}, mix={"ladder": [8]}, peaks={},
                           record=loadgen.Record(window=(10.0, 20.0)),
                           trace=tr)


@pytest.mark.parametrize("metric,span", READERS)
def test_reader_takes_the_median_of_the_window_spans(metric, span):
    host = [Event(span, 11.0, 11.2), Event(span, 12.0, 12.5),
            Event(span, 13.0, 13.1),
            Event(span, 19.9, 23.0),          # starts inside: counted
            Event(span, 9.0, 10.5),           # starts before the window
            Event(span, 20.5, 20.6),          # after it
            Event("bench/submit", 11.0, 14.0),
            Event(span + "/other", 15.0, 18.0)]
    got = harness.reader(metric)(_ctx(host))
    # durations 0.2, 0.5, 0.1, 3.1 → median 0.35 s
    assert got == pytest.approx(350.0)


@pytest.mark.parametrize("metric,span", READERS)
def test_reader_returns_nothing_without_its_span(metric, span):
    ctx = _ctx([Event("bench/submit", 11.0, 14.0),
                Event(span, 25.0, 26.0)])     # outside the window only
    assert harness.reader(metric)(ctx) is None
    ctx.trace = None
    assert harness.reader(metric)(ctx) is None


def test_in_window_matches_whole_names_only():
    tr = Trace(window=(0.0, 1.0),
               host=[Event("mwem/batch/wait", 0.1, 0.2),
                     Event("mwem/batch/waited", 0.3, 0.4),
                     Event("mwem/batch/wait", 1.5, 1.6)])
    assert program_spans.in_window(tr, "mwem/batch/wait") == \
        [Event("mwem/batch/wait", 0.1, 0.2)]
