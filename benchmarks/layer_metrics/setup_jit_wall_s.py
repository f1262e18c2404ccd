"""Wall seconds the process has spent tracing, lowering, compiling or reading
the compile cache when the reader runs (after the traced window, before
the reference): the union of every program's spans in the compile ledger,
so a nested trace is counted once."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    return compiles.wall_s()
