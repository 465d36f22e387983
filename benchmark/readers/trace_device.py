"""Quantities of the device plane of the traced window."""


def read(ctx, quantity: str):
    trace = ctx["trace"]
    if not trace["events"] or not trace["steps"]:
        return None
    if quantity == "op_ms_per_step":
        return trace["op_s"] / trace["steps"] * 1e3
    if quantity == "idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    raise ValueError(f"unknown quantity {quantity!r}")
