"""Fullest held expert over the mean held expert, worst sparse layer, mean
over the steps of the last metrics window (the program's gauge
``moe/expert_load_max_over_mean``): the grouped product's work follows the
sum, a deployment's slowest expert-parallel chip follows the maximum; moves
``samples_per_s_chip``.  A program without the gauge reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    value = get_telemetry().registry.gauge("moe/expert_load_max_over_mean").value
    return value or None
