"""Embedder: mean host milliseconds per ``embed_fn`` call in the window,
each call blocking on its result. Host clock, from the benchmark's
embed probe."""


def read(ctx):
    if not ctx.embeds:
        return None
    return 1e3 * sum(s for _, s in ctx.embeds) / len(ctx.embeds)
