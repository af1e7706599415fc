"""Batches of the measured span assembled into freshly allocated host buffers
(``data/assemble`` records with ``fresh_alloc``): 0 while the loader's ring
recycles; moves ``samples_per_s_chip``."""

from chipbench.layer_metrics import span_window


def read(ctx):
    spans = (span_window.read(ctx) or {}).get("data/assemble")
    if not spans or "fresh_alloc" not in spans[0].attrs:
        return None
    return sum(1 for r in spans if r.attrs.get("fresh_alloc"))
