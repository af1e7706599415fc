"""Calls of the expert layer, over the whole run, whose router sent more pairs
to the held experts than the slot buffers' bound and which therefore ran more
than one window of buffers: the program's counter ``moe/overflow_calls``; 0
where the bound fits the traffic; moves ``samples_per_s_chip``.  A program
that counts slot rows has the counter, and it may stand at 0 (read as 0.0); a
program without ``moe/slot_rows`` has neither and reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    if not registry.counter("moe/slot_rows").value:
        return None
    return float(registry.counter("moe/overflow_calls").value)
