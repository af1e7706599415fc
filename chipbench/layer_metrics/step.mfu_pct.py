"""Required FLOPs per sample (``chipbench/flops/<config>.py``: analytic,
forward + backward, no recompute) x measured rate over the chip's bf16 peak;
moves ``samples_per_s_chip``."""

from chipbench import correct


def read(ctx):
    if not ctx["peaks"]:
        return None
    flops = correct.load_by_name("flops", ctx["cfg"]["name"]).train_flops_per_sample(ctx["cfg"])
    return 100.0 * flops * ctx["rate"] / ctx["peaks"]["bf16_flops_per_s"]
