"""Seeded open-loop arrival processes.

A copy of the generator in the program's ``serving/loadgen.py`` (its
``poisson_trace`` and ``bursty_trace``), kept here so that no change to
the program can change the traffic the benchmark offers. A trace is a
time-sorted list of :class:`ArrivalEvent`; the same seed gives the same
trace.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class ArrivalEvent:
    """One arrival: ``t`` seconds after the trace starts, ``index`` its
    place in the merged, time-sorted trace."""
    t: float
    stream: int
    index: int = 0


def _merge(per_stream_times: list[np.ndarray]) -> list[ArrivalEvent]:
    events = [(float(t), j) for j, times in enumerate(per_stream_times)
              for t in times]
    events.sort(key=lambda e: (e[0], e[1]))
    return [ArrivalEvent(t=t, stream=j, index=i)
            for i, (t, j) in enumerate(events)]


def _counts(n: int, streams: int) -> list[int]:
    return [len(range(j, int(n), streams)) for j in range(streams)]


def poisson_trace(n: int, rate: float, *, seed: int,
                  streams: int = 1) -> list[ArrivalEvent]:
    """``n`` arrivals of independent Poisson streams whose rates add up
    to ``rate`` requests per second."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    rng = np.random.default_rng(seed)
    times = [np.cumsum(rng.exponential(streams / rate, size=c))
             for c in _counts(n, streams)]
    return _merge(times)


def bursty_trace(n: int, rate: float, *, seed: int, streams: int = 1,
                 burst: float = 3.0, duty: float = 0.25,
                 period_s: float = 1.0) -> list[ArrivalEvent]:
    """On/off modulated Poisson arrivals with mean ``rate``: ``duty`` of
    each period runs at ``burst`` times the mean, the rest at the rate
    that keeps the mean. Realised by thinning a Poisson process at the
    peak rate."""
    if not 0 < duty < 1 or burst <= 1 or burst * duty > 1:
        raise ValueError(f"burst={burst} duty={duty}: need 0 < duty < 1, "
                         f"burst > 1 and burst * duty <= 1")
    off = (1.0 - burst * duty) / (1.0 - duty)
    rng = np.random.default_rng(seed)
    times = []
    for c in _counts(n, streams):
        peak = rate / streams * burst
        accepted: list[float] = []
        t = 0.0
        while len(accepted) < c:
            t += float(rng.exponential(1.0 / peak))
            local = burst if (t % period_s) / period_s < duty else off
            if float(rng.random()) * burst < local:
                accepted.append(t)
        times.append(np.asarray(accepted))
    return _merge(times)


PROCESSES = {"poisson": poisson_trace, "bursty": bursty_trace}
