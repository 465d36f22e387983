"""``trace_host_span`` for a span that a sound run may never open (a put
that never finds its queue full): thread-seconds under ``span`` for each
second of the traced window, in %; 0 when it never opened although
``witness``, a span the same program opens for every batch, is there; None
when the witness is missing too (a program that has neither)."""


def read(ctx, span: str, witness: str):
    trace = ctx["trace"]
    if witness not in trace["host"] or not trace["window_s"]:
        return None
    return 100.0 * trace["host"].get(span, [0, 0.0])[1] / trace["window_s"]
