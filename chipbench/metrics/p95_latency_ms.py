"""95th percentile of the due-to-answer latency of the window's
requests, in ms; a failed or unanswered request counts as infinite."""

from chipbench.stats import latencies_ms, percentile


def read(run: dict):
    return percentile(latencies_ms(run), 95)
