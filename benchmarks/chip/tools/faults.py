"""Faults planted in the serving program's sampler, for reading the upper
end of the sampled-token limit (``tools/calibrate.py --fault``) and for
the tests that see ``correct`` come out false.  Each is a function that
takes the program's ``Scheduler._sample`` and returns its faulty
stand-in; greedy requests are left alone, so only the sampled check can
catch them."""
from __future__ import annotations

import dataclasses


def temperature_ignored(inner):
    """Samples at temperature 1 whatever the request asked for."""
    def sample(self, logits_row, req, ntok):
        if req.temperature > 0:
            req = dataclasses.replace(req, temperature=1.0)
        return inner(self, logits_row, req, ntok)
    return sample


def other_row(inner):
    """Samples from the logits row of the sampled request handled before
    (another row of the same step, mostly)."""
    last = [None]

    def sample(self, logits_row, req, ntok):
        if req.temperature <= 0:
            return inner(self, logits_row, req, ntok)
        row, last[0] = last[0], logits_row.copy()
        return inner(self, logits_row if row is None else row, req, ntok)
    return sample


SAMPLER = {"temperature_ignored": temperature_ignored,
           "other_row": other_row}


def plant(name: str, monkeypatch=None):
    """Put the fault ``name`` in place of the program's sampler (through
    ``monkeypatch`` in a test, which undoes it)."""
    from repro.serve import scheduler

    bad = SAMPLER[name](scheduler.Scheduler._sample)
    if monkeypatch is not None:
        monkeypatch.setattr(scheduler.Scheduler, "_sample", bad)
    else:
        scheduler.Scheduler._sample = bad
