"""Commit: mean milliseconds of the window's ``rar.commit`` spans (the
write-ahead log, the store pointer's read, the scatter dispatches and the
broadcast to every replica view of one drain epoch)."""
from bench import program_trace as PT


def read(ctx):
    prog = PT.of(ctx)
    commits = prog.spans.get("rar.commit", []) if prog is not None else []
    return 1e3 * sum(commits) / len(commits) if commits else None
