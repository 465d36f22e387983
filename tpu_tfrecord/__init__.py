"""tpu-tfrecord: a TPU-native TFRecord framework.

A from-scratch re-design of the capabilities of linkedin/spark-tfrecord
(reference: /root/reference) for the JAX/TPU ecosystem:

- TFRecord wire format (length + masked CRC32C framing)  [ref: §2.8, shaded
  org.tensorflow:tensorflow-hadoop]                        -> `tpu_tfrecord.wire`
- tf.Example / tf.SequenceExample protobuf codec (hand-rolled, no TF dep)
  [ref: §2.9, shaded protobuf]                             -> `tpu_tfrecord.proto`
- Schema model (the StructType equivalent)                 -> `tpu_tfrecord.schema`
- Schema-driven row<->record serde
  [ref: TFRecordSerializer.scala / TFRecordDeserializer.scala]
                                                           -> `tpu_tfrecord.serde`
- Schema inference with the numeric-precedence lattice
  [ref: TensorFlowInferSchema.scala]                       -> `tpu_tfrecord.infer`
- Dataset read/write: shard discovery, Hive-style partitionBy, save modes,
  compression codecs [ref: DefaultSource.scala, TFRecordFileReader.scala,
  TFRecordOutputWriter.scala]                              -> `tpu_tfrecord.io`
- TPU ingestion: columnar batches -> sharded jax.Array on a device mesh,
  ragged SequenceExample padding/bucketing, multi-host shard assignment
  (the reference's data-parallel axis, re-imagined for a TPU pod)
                                                           -> `tpu_tfrecord.tpu`
- Stall defense: per-op read/open deadlines, hedged shard reads, the
  pipeline watchdog and the on_stall policy                -> `tpu_tfrecord.stall`
- Deterministic chaos-FS fault injection (seeded FaultPlan + ChaosFS with
  a replayable fault ledger)                               -> `tpu_tfrecord.faults`
- Pipeline flight recorder: span tracing (Chrome-trace export), latency
  histograms, the telemetry pulse + Prometheus endpoint, and the
  producer/consumer bound-ness verdict                     -> `tpu_tfrecord.telemetry`
"""

from tpu_tfrecord.schema import (
    ArrayType,
    BinaryType,
    DataType,
    DecimalType,
    DoubleType,
    FloatType,
    IntegerType,
    LongType,
    NullType,
    StringType,
    StructField,
    StructType,
)
from tpu_tfrecord.options import RecordType, TFRecordOptions
from tpu_tfrecord.registry import lookup_format, register_format
from tpu_tfrecord.retry import RetryPolicy
from tpu_tfrecord.stall import DeadlineError, StallError, WatchdogError

__version__ = "0.1.0"


__all__ = [
    "ArrayType",
    "BinaryType",
    "DataType",
    "DecimalType",
    "DoubleType",
    "FloatType",
    "IntegerType",
    "LongType",
    "NullType",
    "StringType",
    "StructField",
    "StructType",
    "RecordType",
    "TFRecordOptions",
    "RetryPolicy",
    "StallError",
    "DeadlineError",
    "WatchdogError",
    "register_format",
    "lookup_format",
    "__version__",
]
