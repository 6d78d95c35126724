"""JIT inside the window, host clock: milliseconds per window epoch that JAX
spent tracing, lowering or compiling, from the program's JIT record
(``repro.obs.jit_events``). The events are clipped to the window (first
span start to last span end) and merged, since traces nest. 0 when nothing
recompiles; a program without the record reports nothing."""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    events = getattr(obs, "jit_events", None)
    spans, epochs = rec["spans"], rec["counters"].get("epochs")
    if events is None or not spans or not epochs:
        return None
    w0 = min(t0 for _, t0, _ in spans)
    w1 = max(t1 for _, _, t1 in spans)
    total, end = 0.0, w0
    for e in sorted(events(), key=lambda e: e.t0):
        s, t = max(e.t0, end), min(e.t1, w1)
        if t > s:
            total += t - s
            end = t
    return 1e3 * total / epochs
