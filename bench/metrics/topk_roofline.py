"""Memory read: the top-k kernel's share of its roofline. The least time
the chip could take for the reads in the traced window (bytes each read
needs over peak HBM bandwidth; the read is bound by bytes, its operations
take a small fraction of the bf16 peak) over the kernel's device time in
the trace, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    events = ctx.trace.kernel_events.get("topk", [])
    busy = sum(events)
    if not events or busy <= 0:
        return None
    st = ctx.config["store"]
    need = sum(ctx.flops.topk_bytes(st["capacity"], st["embed_dim"], 1)
               for _ in events)
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / busy
