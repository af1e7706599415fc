"""The producer thread's work a step: CPU time (``Span.cpu_ns``) of the
measured span's ``data/assemble`` and ``data/h2d`` spans, summed, over its
steps.  What is left of ``input.assemble_ms`` + ``input.h2d_ms`` is that
thread waiting (for the copy to land, for the interpreter lock), not
working.  Moves ``samples_per_s_chip``.  A program whose spans have no such
slot reads as nothing."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = span_window.read(ctx) or {}
    made = [*spans.get("data/assemble", ()), *spans.get("data/h2d", ())]
    cpu = [getattr(r, "cpu_ns", None) for r in made]
    if not cpu or None in cpu:
        return None
    return sum(cpu) / 1e6 / ctx["steps"]
