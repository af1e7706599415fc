"""Share of the rows the grouped expert product multiplied that were no real
assignment: 100 x (``moe/rows_computed`` - ``moe/assignments_here``) /
``moe/rows_computed``, from the program's counters over the whole run (a
ratio, so the set-up's steps do not bias it).  ``rows_computed`` counts whole
row tiles of the grouped product, so this is the tiles' padding at the group
boundaries; moves ``samples_per_s_chip``.  A program without the counters
reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    rows = registry.counter("moe/rows_computed").value
    here = registry.counter("moe/assignments_here").value
    return 100.0 * (rows - here) / rows if rows else None
