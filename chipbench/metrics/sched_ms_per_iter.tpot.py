"""Host milliseconds in the scheduler's ``form_batch`` per engine
iteration, from the harness's span around it in the traced window."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.span_n.get("form_batch"):
        return None
    return t.span_s["form_batch"] / t.span_n["form_batch"] * 1e3
