"""Whole step: forward-pass operations of both tiers and the embedder in
the traced window over the device's busy time in it at the chip's bf16
peak, in percent. It bounds what any kernel's roofline share can buy."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.busy_s <= 0:
        return None
    flops = sum(ctx.flops.generate_flops(ctx.config[c.tier], len(p),
                                         c.max_new)
                for c in ctx.calls for p in c.prompts)
    flops += len(ctx.embeds) * ctx.flops.embedder_flops(
        ctx.config["embedder"], ctx.prompt_len)
    return 100.0 * flops / (tr.busy_s * ctx.peak["bf16_flops"])
