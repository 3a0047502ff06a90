"""Reader kind `generator`: a number the load generator measured on its
own clock. spec: {"field": name}."""


def read(spec: dict, ctx: dict):
    return ctx["generator"].get(spec["field"])
