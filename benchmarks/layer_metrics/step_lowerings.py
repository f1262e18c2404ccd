"""How often the step was lowered, from the program's compile ledger: 1, or
the step was traced again (in set-up or, worse, inside the window)."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    return float(compiles.summary(ctx["program"].step.fun_name)["lowerings"])
