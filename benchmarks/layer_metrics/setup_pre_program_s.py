"""Seconds from the start of the process to the moment ``grace_tpu`` began
to import, from the program's host ledger (the process's age, by the
kernel's count, when the ledger was made): the interpreter and what the
entry script did first.

**Not comparable across cells**: what it holds follows the order in which
the cell's builder imports. In the ResNet and BERT cells ``run.py`` reaches
the chip (``jax.devices()``) before anything imports ``grace_tpu``, and the
metric is the interpreter, ``import jax`` and reaching the chip (12-19 s).
The decoder cells' builders import ``grace_tpu.models`` before
``jax.devices()`` is called, so there it is the interpreter and ``import
jax`` alone (3 s) and reaching the chip (about 10 s, the part that varies)
stays in the unnamed remainder of ``setup_s``. Compare it between runs of
one cell only. ``host.LEDGER.backends_ready_at_load`` says which of the two
a run was (PERF.md sections 3 and 7: a span around ``jax.devices()`` in
``run.py`` is the next ``benchmark`` issue's first item)."""


def read(ctx):
    try:
        from grace_tpu.telemetry import host
    except ImportError:                 # a program without the host ledger
        return None
    return host.LEDGER.pre_program_s()
