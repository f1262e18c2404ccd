"""Programs this run compiled and wrote to the persistent cache, from the
compile ledger's count of JAX's ``cache_misses`` events: 0 on a warm run;
anything else says this run's set-up was a cold one."""


def read(ctx):
    try:
        from grace_tpu.telemetry import compiles
    except ImportError:                 # a program without the compile ledger
        return None
    return float(compiles.counts()["cache_misses"])
