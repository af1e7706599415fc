"""Calls of the flash-attention kernels (``tpuframe_flash*``) per step, from the
trace: one forward and one backward call (two where the backward takes two
passes) for every attention layer that took the kernels; moves ``samples_per_s_chip``.  A program without such kernels
reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    flash = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_flash")]
    return sum(k["calls"] for k in flash) / t["steps"] if flash else None
