"""Share of the window's lanes that carried a request: live lanes over
live plus padded lanes, from the engine's counters, in %."""


def read(run: dict):
    a, b = run["counters_start"], run["counters_end"]
    live = b["live_lanes"] - a["live_lanes"]
    pad = b["padded_lanes"] - a["padded_lanes"]
    return 100.0 * live / (live + pad) if live + pad else None
