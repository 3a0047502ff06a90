"""Reader kind `prom`: a ratio of two /metrics counters' growth over the
window. spec: {"delta": name, "per_delta": name, "scale": 1.0}."""


def read(spec: dict, ctx: dict):
    def delta(name):
        return ctx["prom1"].get(name, 0.0) - ctx["prom0"].get(name, 0.0)

    per = delta(spec["per_delta"])
    if per <= 0:
        return None
    return delta(spec["delta"]) / per * spec.get("scale", 1.0)
