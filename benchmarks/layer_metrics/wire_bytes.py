"""Bytes one chip sends per step, from the shapes of the cell's gradients
(``grace_tpu.utils.metrics.wire_report``: a count, not a measurement). A
dense exchange compresses nothing and has nothing to read."""


def read(ctx):
    from grace_tpu.utils.metrics import wire_report

    program = ctx["program"]
    if program.cell["grace"]["compressor"] == "none":
        return None
    return float(wire_report(program.grace.compressor,
                             program.state.params).wire_bytes)
