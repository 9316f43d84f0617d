"""Decision core: host milliseconds in ``rar.decide`` spans (partition,
prompt and outcome assembly, the drain's alignment and settle loops) per
microbatch of the window."""
from bench import program_trace as PT


def read(ctx):
    prog = PT.of(ctx)
    if prog is None or not prog.batches:
        return None
    return 1e3 * sum(prog.spans.get("rar.decide", [])) / len(prog.batches)
