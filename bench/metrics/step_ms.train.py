"""Training step, host clock: mean milliseconds from the step's dispatch to
its host reads (MLL, overflow flags, finite gradients), over the window."""


def read(rec):
    d = [t1 - t0 for name, t0, t1 in rec["spans"] if name == "step"]
    return 1e3 * sum(d) / len(d) if d else None
