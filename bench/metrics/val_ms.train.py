"""Validation posterior, host clock: mean milliseconds from its dispatch to
the RMSE read, over the window's epochs."""


def read(rec):
    d = [t1 - t0 for name, t0, t1 in rec["spans"] if name == "validation"]
    return 1e3 * sum(d) / len(d) if d else None
