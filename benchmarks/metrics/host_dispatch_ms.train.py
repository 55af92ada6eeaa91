"""Host side of a training step: the benchmark's own span around the
un-waited ``step`` call, median over the window, in milliseconds."""
from benchmarks.harness import stats


def read(ctx):
    spans = ctx.kind.window_spans.get("step")
    if not spans:
        return None
    return 1e3 * stats.median(spans)
