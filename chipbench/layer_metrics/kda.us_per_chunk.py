"""Device time of the vector-decay delta rule's kernels for each chunk step
they walk: ``kda.kernel_ms`` over the chunk steps of a training step.  The
program counts, on the host and once a trace of the model, the chunk steps of
a layer's call, forward and backward together (``kda/chunks``: chunks x heads
x rows x 2) and the calls (``kda/calls``); their quotient times the forward
calls a step that the trace holds is the chunk steps a step, however often
the model was traced.  Moves ``samples_per_s_chip``.  A program without the
counters or the kernels reads as nothing."""

from chipbench import correct


def read(ctx):
    from tpuframe.track.telemetry import get_telemetry

    t = ctx["trace"]
    ms = correct.load_by_name("layer_metrics", "kda.kernel_ms").read(ctx)
    registry = get_telemetry().registry
    chunks = registry.counter("kda/chunks").value
    calls = registry.counter("kda/calls").value
    if ms is None or not chunks or not calls:
        return None
    forward = t["kernels"].get("tpuframe_kda_fwd")
    if not forward:
        return None
    return 1e3 * ms / (chunks / calls * forward["calls"] / t["steps"])
