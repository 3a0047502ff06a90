"""Which daemons of a ring a per-layer metric reads. A metric file may
say `"node": i` (default 0) or `"node": "all"`; a configuration of one
daemon has node 0 alone, and its numbers stand at the top of `ctx`."""


def chosen(spec: dict, ctx: dict) -> list:
    """The chosen nodes' {"stages0", "stages1", "prom0", "prom1"}; none
    for a node the configuration does not have (the metric reads nothing)."""
    nodes = ctx.get("nodes") or [ctx]
    which = spec.get("node", 0)
    return nodes if which == "all" else nodes[which:which + 1]
