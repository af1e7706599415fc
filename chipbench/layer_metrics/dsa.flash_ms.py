"""Device time of the flash-attention kernels under the chosen-keys rule per
step, from the trace: the ``tpuframe_flash_fwd_select`` / ``_bwd_select`` Pallas
custom calls (the rule's calls carry its suffix; one forward and one backward
an attention layer, 4 + 4 a step in ``keyevl2_seq8192``):
``attention.flash_ms``'s reading of those calls alone.  Moves
``samples_per_s_chip``.  A program without such kernels reads as nothing."""

from chipbench import correct


def select_calls(ctx) -> dict:
    """``ctx`` with the traced kernels that ran under the chosen-keys rule alone."""
    t = ctx["trace"]
    if not t:
        return ctx
    kernels = {n: k for n, k in t["kernels"].items() if n.endswith("_select")}
    return {**ctx, "trace": {**t, "kernels": kernels}}


def read(ctx):
    return correct.load_by_name("layer_metrics", "attention.flash_ms").read(select_calls(ctx))
