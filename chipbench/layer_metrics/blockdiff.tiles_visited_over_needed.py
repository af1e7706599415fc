"""Tiles the attention sweeps visit under the block-diffusion mask over the
tiles' worth of scores that count: the program's counters
``attention/tiles_visited`` / ``attention/tiles_needed`` over the whole run (a
ratio of two static counts a step, both in tiles of the backward kernel's
side, forward and backward together).  1.0 is no waste: every score computed
is one the mask asks for.  Moves ``samples_per_s_chip``.  A program without
the counters reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    needed = registry.counter("attention/tiles_needed").value
    visited = registry.counter("attention/tiles_visited").value
    return visited / needed if needed else None
