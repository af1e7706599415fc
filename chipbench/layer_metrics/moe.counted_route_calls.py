"""Calls of the expert layer a step whose plan of slots was made by counting
(``models/moe.py::_window_plan``: no sort, gather or scatter over the (token,
choice) pairs; every layer whose slot buffers are shorter than its pairs): the
program's counter ``moe/counted_routes`` over the whole run, over the steps the
run dispatched (the ``train/step`` spans; every step's counters are drained by
the end of ``fit()``); 4 in each expert cell, one an expert layer; moves
``samples_per_s_chip``.  A program that sorts its pairs has no such counter and
reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    counted = registry.counter("moe/counted_routes").value
    steps = registry.histogram("span/train/step").count
    return counted / steps if counted and steps else None
