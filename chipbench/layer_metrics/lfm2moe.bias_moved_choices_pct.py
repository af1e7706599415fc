"""Share of the (token, choice) pairs whose expert the selection bias chose
and the score alone would not have: 100 x ``moe/bias_moved_choices`` /
``moe/bias_choices`` (tokens x choices a token x expert layers), from the
program's counters over the whole run (a ratio, so the set-up's steps do not
bias it).  0 is a bias that does nothing; the bias moves which experts work,
never their weights, so it moves ``samples_per_s_chip`` through the rows
routed here.  A program without the counters reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    chosen = registry.counter("moe/bias_choices").value
    return 100.0 * registry.counter("moe/bias_moved_choices").value / chosen if chosen else None
