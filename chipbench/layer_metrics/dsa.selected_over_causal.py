"""(query, key) pairs the index chose over the pairs with the key not after the
query: ``attention/pairs_selected`` / ``attention/pairs_causal``, from the
program's counters over the whole run (a ratio, so the set-up's steps do not
bias it).  The first is counted on the device from what the index op wrote, a
step and layer; the second is the rows' triangle.  ``sum over t of min(t + 1,
topk)`` over ``L (L + 1) / 2``: 14,681,088 / 33,558,528 = 0.437477 at 8192
positions and 2048 keys a query, 1 where the rows are no longer than ``topk``
and the layer is plain causal attention; any other reading says the choice is
not exactly ``topk``.  The
attention kernels' required work follows it, so it moves
``samples_per_s_chip``.  A program without the counters reads as nothing."""


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    registry = get_telemetry().registry
    causal = registry.counter("attention/pairs_causal").value
    return registry.counter("attention/pairs_selected").value / causal if causal else None
