"""Assignments of a token to an expert held here that the last traced step
computed, summed over the expert layers: the ``held`` counters the
program's model state carries. A program whose model state has no such
counter has nothing to read."""


def counter(ctx, name):
    state = getattr(ctx["program"].state, "model_state", None)
    layers = state.get("layers", []) if isinstance(state, dict) else []
    values = [float(layer[name]) for layer in layers
              if isinstance(layer, dict) and name in layer]
    return sum(values) if values else None


def read(ctx):
    return counter(ctx, "held")
