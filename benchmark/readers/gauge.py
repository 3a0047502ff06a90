"""Reader kind `gauge`: one /metrics gauge's level over another's at the
end of the read window (the `prom` reader differences counters; a level
is read, not differenced).

spec: {"level": name, "per_level": name, "scale": 1.0}
A program that exports neither gauge (the parent of the PR that added
them), or a backend that keeps no such statistic (the CPU reports no
peak: the gauge reads 0), reads as nothing.
"""


def read(spec: dict, ctx: dict):
    level = ctx["prom1"].get(spec["level"])
    per = ctx["prom1"].get(spec["per_level"])
    if not level or not per:
        return None
    return level / per * spec.get("scale", 1.0)
