"""Rows the expert layer's slot buffers carried for each (token, choice) pair
routed to an expert held here: ``moe/slot_rows`` / ``moe/assignments_here``,
from the program's counters over the whole run (a ratio, so the set-up's
steps do not bias it).  Every elementwise pass, gather and saved array round
the grouped products is as long as the buffers, so 1.0 is no waste; a slot
for every pair reads ``experts / held`` (8 where a chip holds an eighth of
them); moves ``samples_per_s_chip``.  A program without the counter reads as
nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    slots = registry.counter("moe/slot_rows").value
    here = registry.counter("moe/assignments_here").value
    return slots / here if slots and here else None
