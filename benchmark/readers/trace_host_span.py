"""A span on the host's plane of the traced window, the benchmark's or the
program's own: thread-seconds under it for each second of the window, in %
(more than one thread under the span can pass 100)."""


def read(ctx, span: str):
    trace = ctx["trace"]
    if span not in trace["host"] or not trace["window_s"]:
        return None
    return 100.0 * trace["host"][span][1] / trace["window_s"]
