"""Device time of the index of sparse attention per step, from the trace: the
``tpuframe_index_*`` Pallas custom calls (the index scores of a tile of
queries and the exact choice of ``topk`` keys a query; forward only, one call
an attention layer whose rows are longer than ``topk``: 4 a step in
``keyevl2_seq8192``).  The index's three projections and key norm, XLA's own
ops under the same ``tpuframe/attn/index`` scope, are not in it: the trace's
reduction keeps kernels by name and no scopes.  Moves ``samples_per_s_chip``.
A program without such kernels reads as nothing."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_index")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
