"""Device forwards per applied mutation: the engine's logits-cache misses
(each a full-graph forward) over its mutations, window only."""


def read(ctx):
    c = ctx["counters"]
    return c["logits_cache_misses"] / c["mutations"] if c["mutations"] else None
