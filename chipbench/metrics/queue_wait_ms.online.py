"""Mean wait of the window's answered requests from when each was due
to the start of the tick that served it, in ms (harness spans)."""


def read(run: dict):
    waits = [(r["start"] - r["due"]) * 1e3 for r in run["log"].req.values()
             if r["due"] < run["seconds"] and r["ok"]]
    return sum(waits) / len(waits) if waits else None
