"""Serve path (host): blocking device-to-host fetches per microbatch of
the window (embeds, store reads, one per tier call's length group, the
commit's pointer read), from the ``syncs`` that each ``rar.batch`` span
carries, the program's ``host/syncs/*`` counts made inside it."""
from bench import program_trace as PT


def read(ctx):
    prog = PT.of(ctx)
    syncs = [b["syncs"] for b in prog.batches if "syncs" in b] \
        if prog is not None else []
    return sum(syncs) / len(syncs) if syncs else None
