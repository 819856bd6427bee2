"""Model FLOPs per image times images answered per second in the
window, over the chip's bf16 peak, in %: the whole step's share of the
peak, whatever kernels run."""

from chipbench.stats import completed_in_window


def read(run: dict):
    n = completed_in_window(run)
    if not n:
        return None
    return 100.0 * run["flops_per_image"] * n / run["seconds"] / run["peak_flops"]
