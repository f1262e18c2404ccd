"""Seconds spent reading and deserialising entries of the persistent
compile cache: JAX's ``/jax/compilation_cache/cache_retrieval_time_sec``
durations, summed by the program's compile ledger. Part of
``setup_jit_wall_s`` (each read lies inside its program's compile span);
0 on a run that found nothing in the cache."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    durations = getattr(compiles, "durations", None)
    return None if durations is None else durations()["cache_read_s"]
