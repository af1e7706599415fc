"""Device time of the vector-decay delta rule's chunk-local kernels (the
``tpuframe_kdachunk_*`` Pallas custom calls: what the schedule computes inside
a chunk, from ``g``'s running sum to ``U`` and ``W``; one forward, one again
and one transposed a ``kda`` layer, 12 a step in ``kimilinear_seq4096``) per
step, from the trace; moves ``samples_per_s_chip``.  The pass over the chunks
(``tpuframe_kda_*``) is ``kda.kernel_ms``'s and is not in it.  A program that
leaves the chunk-local part to XLA's fusions has no such kernel and reads as
nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_kdachunk_")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
