"""Median per-step completion interval of the measured span; beside the
end-to-end p90 it says whether the tail or the body moved; moves
``step_ms_p90``."""

from chipbench import windows


def read(ctx):
    return windows.median(ctx["intervals_ms"]) if ctx["intervals_ms"] else None
