"""Mean host time of one graph patch: the graph store's summed patch
milliseconds over its patches, window only."""


def read(ctx):
    c = ctx["counters"]
    return c["graph_patch_ms_total"] / c["graph_patches"] \
        if c["graph_patches"] else None
