"""Host CPU seconds (this process and its children, user + system) over
the window, for each million examples consumed."""


def read(ctx):
    measured = ctx["measured"]
    if not measured["rows"]:
        return None
    return measured["cpu_s"] / (measured["rows"] / 1e6)
