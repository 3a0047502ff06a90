"""Reader kind `gauge`: one /metrics gauge's level over another's at the
end of the read window (the `prom` reader differences counters; a level
is read, not differenced).

spec: {"level": name, "per_level": name, "scale": 1.0}
A program that exports neither gauge (the parent of the PR that added
them), or a backend that keeps no such statistic (the CPU reports no
peak: the gauge reads 0), reads as nothing. With "node": "all" both
levels are summed over the ring's nodes (readers/nodes.py).
"""

from readers import nodes


def read(spec: dict, ctx: dict):
    chosen = nodes.chosen(spec, ctx)
    level = sum(n["prom1"].get(spec["level"]) or 0.0 for n in chosen)
    per = sum(n["prom1"].get(spec["per_level"]) or 0.0 for n in chosen)
    if not level or not per:
        return None
    return level / per * spec.get("scale", 1.0)
