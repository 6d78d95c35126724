"""Whole training step: the least time of the step's work at the chip's
peaks (``bench/counts.train_step``: the larger of FLOPs over peak FLOP/s
and compulsory bytes over peak bandwidth) over the measured mean step
time. It bounds every kernel's share: a later change that takes a kernel
off the path still has to move this."""
from bench import counts


def read(rec):
    o = rec["objects"]
    d = [t1 - t0 for name, t0, t1 in rec["spans"] if name == "step"]
    if not d or not o.get("cg_iters"):
        return None
    r = o["model"].config.order
    flops, nbytes = counts.train_step(o["n"], o["d"], o["m"], o["c"],
                                      o["cg_iters"], r)
    return 100.0 * counts.least_seconds(flops, nbytes, rec["peak"]) / (
        sum(d) / len(d))
