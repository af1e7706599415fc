"""``memory_analysis()`` temporaries of the compiled train step, per device;
moves ``peak_hbm_gib``."""


def read(ctx):
    return ctx["memory"]["temp"] / 2**30
