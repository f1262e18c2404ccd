"""Seconds of Python tracing of the step to a jaxpr: the step's own
``jaxpr_trace_duration`` events, from the program's compile ledger. The
``jnp`` functions the step calls are traced inside this interval."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    return compiles.summary(ctx["program"].step.fun_name)["trace_s"]
