"""Device idle share of the training window: 1 - busy / window, busy the
union of the device-op intervals in the profiler trace."""


def read(rec):
    t = rec.get("trace")
    if not t or not t["busy_s"] or t["idle_share"] is None:
        return None
    return 100.0 * t["idle_share"]
