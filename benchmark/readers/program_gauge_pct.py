"""A share the program says of itself, in %: a gauge of its own registry
(``tpu_tfrecord.metrics.METRICS``) that holds a share of 1, times 100, as it
stands when the run is over (``program_gauge`` reads a gauge as it is). None
where the program never set it: a program from before the gauge existed."""


def read(ctx, gauge: str):
    from tpu_tfrecord.metrics import METRICS

    share = METRICS.gauge_value(gauge)
    return None if share is None else 100.0 * share
