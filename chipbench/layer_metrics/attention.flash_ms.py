"""Device time of the flash-attention kernels (the ``tpuframe_flash*`` Pallas
custom calls under ``blockwise_attention``: forward and backward) per step, from
the trace; moves ``samples_per_s_chip``.  A program without such kernels reads
as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    flash = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_flash")]
    return 1e3 * sum(k["seconds"] for k in flash) / t["steps"] if flash else None
