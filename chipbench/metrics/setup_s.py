"""Seconds from process start to the window's start: imports, device
start-up, weights, the engine and the warm-up of the cell's shapes."""


def read(run: dict):
    return run["setup_s"]
