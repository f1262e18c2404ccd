"""Positions the last traced step scored: the tokens block-diffusion
training masked in the noised copies of the chip's sequences, the ``masked``
counter the program's model state carries. A program whose model state has
no such counter has nothing to read."""


def read(ctx):
    state = getattr(ctx["program"].state, "model_state", None)
    if not isinstance(state, dict) or "masked" not in state:
        return None
    return float(state["masked"])
