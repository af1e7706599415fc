"""Device time of the kernels that make what the gated delta rule reads (the
``tpuframe_conv_silu_*`` Pallas custom calls: the four-tap convolution, SiLU
and the unit norms of q and k from the fused projection's output, one forward
and one backward a ``linear_attention`` layer, 3 + 3 a step in
``qwen3next_seq8192``) per step, from the trace; moves ``samples_per_s_chip``.
A program that leaves that work to XLA's fusions has no such kernels and reads
as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_conv_silu")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
