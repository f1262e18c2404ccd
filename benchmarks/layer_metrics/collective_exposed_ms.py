"""Per step, the time collective operations hold the device while no
compute operation runs on it. Read only where an exchange exists."""


def read(ctx):
    if ctx["program"].world == 1:
        return None
    return ctx["reduced"]["collective_exposed_s_per_step"] * 1e3
