"""Requests answered inside the window, over the window's length."""

from chipbench.stats import completed_in_window


def read(run: dict):
    return completed_in_window(run) / run["seconds"]
