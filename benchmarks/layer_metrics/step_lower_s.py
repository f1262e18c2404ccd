"""Seconds of lowering the step's jaxpr to an MLIR module: the step's own
``jaxpr_to_mlir_module_duration`` events, from the program's compile
ledger."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    return compiles.summary(ctx["program"].step.fun_name)["lower_s"]
