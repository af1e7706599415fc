"""Device time of the expert layer's un-sort kernel (the ``tpuframe_unsort*``
Pallas custom calls: every token's routed rows summed out of the sorted slots,
one in the forward and one in the backward pass of an expert layer, 8 a step in
each expert cell) per step, from the trace; moves ``samples_per_s_chip``.  A
program that leaves the work to XLA's fusions (a gather of a row a (token,
choice) pair and its reduce) has no such kernel and reads as nothing.

The kernel alone: what ``ops/unsort.py`` leaves to XLA beside it is outside the
reading (the plan, a compare-and-sum over a (slots, tiles x held) table once a
layer, and the ``place`` array each call fetches its windows' tokens from:
about a third more, 0.4-0.5 ms a step, PR 48), so a regression there shows in
``step.device_ms`` and the breakdown's fusion rows, not here."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    calls = [k for n, k in t["kernels"].items() if n.startswith("tpuframe_unsort")]
    return 1e3 * sum(k["seconds"] for k in calls) / t["steps"] if calls else None
