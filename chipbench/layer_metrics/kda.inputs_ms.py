"""Device time of the kernels that make what the vector-decay delta rule reads
(the ``tpuframe_conv_silu_*`` Pallas custom calls: the four-tap convolution,
SiLU and the unit norms of q and k from the fused projection's output at 32
key heads, one forward and one backward a ``kda`` layer, 4 + 4 a step in
``kimilinear_seq4096``) per step, from the trace; moves
``samples_per_s_chip``.  The calls are ``deltanet.inputs_ms``'s, and so is
the reading; a program that leaves that work to XLA's fusions has no such
kernels and reads as nothing."""

from chipbench import correct


def read(ctx):
    return correct.load_by_name("layer_metrics", "deltanet.inputs_ms").read(ctx)
