"""Milliseconds a step of a span the program itself opens."""


def trace_span_ms_per_step(rec, span, witness):
    """The durations of every span of that full name ("ray_tpu.host.gc")
    summed inside the window of whole step programs (the first device's, on
    the trace's one clock; a span the window's edge cuts counts its part
    inside), over the window's steps. 0.0 where the trace holds none of them
    and does hold a span named `witness`, one the same emitter opens all the
    while ("ray_tpu.host.heartbeat"): a program that opens neither (an older
    one, a renamed span, a hook that was not installed) says nothing, which is
    None, as without a trace or without a whole step in it."""
    tr = rec["trace"]
    if not tr or not tr.steps:
        return None
    start, end = tr.devices[0].start, tr.devices[0].end
    inside = [max(0, min(s + d, end) - max(s, start))
              for name, s, d, _ in tr.program if name == span]
    if not inside and not any(name == witness for name, _, _, _ in tr.program):
        return None
    return sum(inside) / 1e6 / tr.steps
