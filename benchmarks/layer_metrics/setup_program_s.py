"""Seconds of the program's own Python on the set-up path: the self times
of the host ledger's spans summed (the import of ``grace_tpu``,
``place_compile_cache``, ``grace_from_params``, the transform's and the
step's build, the state's initialisers), each span's length less its child
spans and less the compile ledger's intervals inside it, so nothing JAX
times as tracing, lowering or compiling is counted here."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles, host
    except ImportError:                 # a program without the host ledger
        return None
    return host.LEDGER.program_s(compiles.intervals())
