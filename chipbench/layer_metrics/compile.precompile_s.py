"""Wall time of the Trainer's AOT precompile (its report's ``wall_s``);
moves ``setup_s``.  Nothing to read where the report is missing."""


def read(ctx):
    return ctx["precompile_wall_s"]
