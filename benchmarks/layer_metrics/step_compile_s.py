"""Seconds of the step's ``backend_compile_duration`` events, from the
program's compile ledger: XLA compiling the step, or reading it from the
persistent cache (``compile_cache_misses`` says which)."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    return compiles.summary(ctx["program"].step.fun_name)["compile_s"]
