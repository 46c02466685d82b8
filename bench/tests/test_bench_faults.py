"""With the timed path broken underneath, a run comes out not correct.

Each fault a cell can have is planted in the program (the harness is left
as it is) and a tiny cell runs end to end on the CPU:

* a step that returns its state unchanged: function B returns the running
  prefix as it was;
* half of the batch left out: each chunk's second half is never fed;
* an answer altered where it is produced: every registration is moved by
  3 px;
* an answer that never comes: the window's first session fails to open,
  or fails at its first feed, and the sessions after it run sound.

The exchange between chips does not exist here: every cell runs on one.
"""

import pytest

from bench_tiny import run_tiny, tiny_cell

SERIES = ["series_drift", "series_burst"]


def _series_state_unchanged(mp):
    from repro.core.registration import RegElement, RegistrationOperator

    mp.setattr(RegistrationOperator, "__call__",
               lambda self, a, b: RegElement(a.deformation, a.i, b.k))


def _series_half_left_out(mp):
    from repro.service import SeriesSession

    feed = SeriesSession.feed
    mp.setattr(SeriesSession, "feed",
               lambda self, chunk: feed(self, chunk[:chunk.shape[0] // 2]))


def _series_answer_altered(mp):
    import repro.core.registration as registration
    import repro.service as service

    pair = registration.register_pair

    def altered(ref, tmpl, init=None, cfg=registration.RegistrationConfig()):
        res = pair(ref, tmpl, init, cfg)
        d = dict(res.deformation, shift=res.deformation["shift"] + 3.0)
        return res._replace(deformation=d)

    mp.setattr(registration, "register_pair", altered)
    mp.setattr(service, "register_pair", altered)


def _series_fails(mp, at):
    """The second session opened, the window's first (the warm-up opens
    one), raises at ``open_series`` or at its first feed."""
    import repro
    from repro.service import SeriesSession

    opened = []
    open_series = repro.open_series

    def opening(*args, **kw):
        opened.append(1)
        if at == "open" and len(opened) == 2:
            raise RuntimeError("planted: open_series fails")
        return open_series(*args, **kw)

    mp.setattr(repro, "open_series", opening)
    if at == "feed":
        feed = SeriesSession.feed

        def failing(self, chunk):
            if len(opened) == 2:
                raise RuntimeError("planted: feed fails")
            return feed(self, chunk)

        mp.setattr(SeriesSession, "feed", failing)


SERIES_FAULTS = {
    "state_unchanged": _series_state_unchanged,
    "half_left_out": _series_half_left_out,
    "answer_altered": _series_answer_altered,
    "fails_to_open": lambda mp: _series_fails(mp, "open"),
    "fails_at_first_feed": lambda mp: _series_fails(mp, "feed"),
}


@pytest.mark.parametrize("fault", sorted(SERIES_FAULTS))
@pytest.mark.parametrize("name", SERIES)
def test_series_fault_is_not_correct(monkeypatch, name, fault):
    SERIES_FAULTS[fault](monkeypatch)
    out, lines = run_tiny(tiny_cell(name))
    assert not out["correct"], "\n".join(lines)
    if fault.startswith("fails"):
        assert out["checks"]["series_failed"]["value"] == 1
        assert out["failed"] >= 1
