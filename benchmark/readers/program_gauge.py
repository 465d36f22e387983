"""A gauge of the program's own registry (``tpu_tfrecord.metrics.METRICS``)
as it stands when the run is over: what the program said of itself while it
was traced (how many layers took a kernel) rather than how long anything
took. None where the program never set it."""


def read(ctx, gauge: str):
    from tpu_tfrecord.metrics import METRICS

    return METRICS.gauge_value(gauge)
