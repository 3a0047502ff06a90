"""Reader kind `prom_sum`: the growth of some /metrics counters over the
growth of others, inside the window.

spec: {"delta": [name, ...], "per_delta": [name, ...], "scale": 1.0}
value = sum of the first list's growth / sum of the second's * scale.
A program that does not export one of the named counters (the parent of
the PR that added it) reads as nothing, and so does a window in which
the denominator did not move. With "node": "all" the growths are summed
over the ring's nodes (readers/nodes.py).
"""

from readers import nodes


def read(spec: dict, ctx: dict):
    names = [*spec["delta"], *spec["per_delta"]]
    chosen = nodes.chosen(spec, ctx)
    if any(name not in n["prom1"] for name in names for n in chosen):
        return None

    def growth(which):
        return sum(n["prom1"][name] - n["prom0"].get(name, 0.0)
                   for name in which for n in chosen)

    per = growth(spec["per_delta"])
    if per <= 0:
        return None
    return growth(spec["delta"]) / per * spec.get("scale", 1.0)
