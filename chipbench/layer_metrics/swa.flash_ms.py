"""Device time of the flash-attention kernels of the sliding-window layers per
step, from the trace: the ``tpuframe_flash_fwd_window`` / ``_bwd_window`` Pallas
custom calls (a band rule's calls carry its suffix; one forward and one
backward a window layer, 3 + 3 a step in ``mellum2_seq8192``):
``attention.flash_ms``'s reading of those calls alone.  Moves
``samples_per_s_chip``.  A program without such kernels reads as nothing."""

from chipbench import correct


def window_calls(ctx) -> dict:
    """``ctx`` with the traced kernels that ran under the band rule alone."""
    t = ctx["trace"]
    if not t:
        return ctx
    kernels = {n: k for n, k in t["kernels"].items() if n.endswith("_window")}
    return {**ctx, "trace": {**t, "kernels": kernels}}


def read(ctx):
    return correct.load_by_name("layer_metrics", "attention.flash_ms").read(window_calls(ctx))
