"""Device time per served tick, in ms: the union of the device's op
intervals in the profiled window over the ticks served there. The
profiler slows the host, not the device, so this reads the same as in
an untraced window where every tick is a full batch."""


def read(run: dict):
    tr = run.get("trace")
    if tr is None or not tr["ticks"]:
        return None
    return 1e3 * tr["busy_s"] / tr["ticks"]
