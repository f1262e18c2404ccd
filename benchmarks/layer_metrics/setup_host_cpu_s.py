"""CPU seconds of the process, every thread, user and system, from its
start to the moment the last program of set-up was built (the host ledger's
snapshot at the end of the last ``backend_compile_duration`` event). XLA
compiles on many threads, so this may pass the wall time; beside
``setup_jit_wall_s`` it says how much of set-up the host computes and how
much it waits."""


def read(ctx):
    try:
        from grace_tpu.telemetry import host
    except ImportError:                 # a program without the host ledger
        return None
    built = host.LEDGER.built
    return None if built is None else built.cpu
