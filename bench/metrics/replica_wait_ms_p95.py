"""Fabric queue: the 95th percentile, over the window's microbatches, of
the wait in the replica's FIFO from the fabric's submit to the worker's
dequeue (the program's ``Ticket.started - Ticket.submitted``, carried as
``wait_us`` on each ``rar.batch`` span), in ms."""
import numpy as np

from bench import program_trace as PT


def read(ctx):
    prog = PT.of(ctx)
    waits = [b["wait_us"] for b in prog.batches if "wait_us" in b] \
        if prog is not None else []
    return float(np.percentile(waits, 95)) / 1e3 if waits else None
