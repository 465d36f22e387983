"""Host spans of the traced window, the program's own or the benchmark's:
total seconds under ``spans`` for each occurrence of ``per``, in ms (the
dispatch of a batch's copy plus the wait for it to land, per dispatch)."""


def read(ctx, spans, per: str):
    host = ctx["trace"]["host"]
    if not host.get(per, [0])[0]:
        return None
    return sum(host[s][1] for s in spans if s in host) / host[per][0] * 1e3
