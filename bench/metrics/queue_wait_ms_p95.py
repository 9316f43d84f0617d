"""Admission wait: the 95th percentile, over the window's requests, of
the time from a request's due instant to its microbatch's submit to the
fabric (the scheduler's close rule plus any lateness of the generator).
Host clock, from the benchmark's fabric proxy."""
import numpy as np


def read(ctx):
    waits = [(w["submitted"] - w["due"]) * 1e3 for w in ctx.window
             if w["submitted"] is not None]
    return float(np.percentile(waits, 95)) if waits else None
