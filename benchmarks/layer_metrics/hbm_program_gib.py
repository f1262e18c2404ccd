"""GiB per chip the compiled step holds: arguments + outputs − aliased +
temporaries, from the compiler's own ``memory_analysis()`` of the program
the window runs. A count that repeats exactly."""


def read(ctx):
    return ctx["program"].hbm_program_bytes() / 2 ** 30
