"""Mean host time of one serving ``step()`` in the window, in ms:
admission, guards, the image stack and its copy, the program and the
logits pull."""

from chipbench.stats import window_ticks


def read(run: dict):
    ticks = window_ticks(run)
    return 1e3 * sum(b - a for a, b, _ in ticks) / len(ticks) if ticks else None
