"""OP001: a kernel module that never made it into OPS_REGISTRY —
invisible to the doctor and the diagnosis's name map."""


def fused_rogue(x):
    return x
