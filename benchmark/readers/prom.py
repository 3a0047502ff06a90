"""Reader kind `prom`: a ratio of two /metrics counters' growth over the
window. spec: {"delta": name, "per_delta": name, "scale": 1.0}; with
"node": "all" both are summed over the ring's nodes (readers/nodes.py)."""

from readers import nodes


def read(spec: dict, ctx: dict):
    def delta(name):
        return sum(n["prom1"].get(name, 0.0) - n["prom0"].get(name, 0.0)
                   for n in nodes.chosen(spec, ctx))

    per = delta(spec["per_delta"])
    if per <= 0:
        return None
    return delta(spec["delta"]) / per * spec.get("scale", 1.0)
