"""Weak tier: forward-pass operations of the weak tier's calls in the
window over their host wall time at the chip's bf16 peak, in percent."""


def read(ctx):
    calls = [c for c in ctx.calls if c.tier == "weak"]
    secs = sum(c.seconds for c in calls)
    if not calls or secs <= 0:
        return None
    cfg = ctx.config["weak"]
    flops = sum(ctx.flops.generate_flops(cfg, len(p), c.max_new)
                for c in calls for p in c.prompts)
    return 100.0 * flops / (secs * ctx.peak["bf16_flops"])
