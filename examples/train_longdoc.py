#!/usr/bin/env python
"""End-to-end example: train the long-document classifier on SequenceExamples.

The long-context twin of examples/train_dlrm.py — covers the ragged path of
the framework surface:
  1. generate ragged SequenceExample documents (variable-length FeatureLists)
  2. stream them with TFRecordDataset (recordType=SequenceExample)
  3. pad/bucket frames to dense [B, L, D] + lengths, assemble seq-sharded
     global batches over a dp x sp mesh
  4. jit train steps whose attention runs as RING ATTENTION over the 'seq'
     axis; checkpoint the input position
  5. resume from the saved state (identity-fingerprinted)

Run on any JAX backend; for a local simulation:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_longdoc.py
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tpu_tfrecord import compile_cache

compile_cache.enable()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache

import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _harness

import tpu_tfrecord.io as tfio
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.models import long_doc
from tpu_tfrecord.schema import (
    ArrayType,
    FloatType,
    LongType,
    StructField,
    StructType,
)
from tpu_tfrecord.tpu import make_global_batch
from tpu_tfrecord.tpu.mesh import create_mesh

SEQ_DIM = 16
MAX_LEN = 64
BATCH = 64


def make_schema() -> StructType:
    return StructType(
        [
            StructField("label", LongType(), nullable=False),
            StructField("frames", ArrayType(ArrayType(FloatType()))),
        ]
    )


def generate(data_dir: str, shards: int = 4, rows: int = 256) -> None:
    """Ragged documents whose label depends on the (variable-length)
    content, written through the io layer as SequenceExamples. ONE write
    job (sharded via max_records_per_file) so _SUCCESS appears only after
    ALL shards committed — a kill mid-generation can never leave a
    marker over a partial dataset."""
    if os.path.exists(os.path.join(data_dir, "_SUCCESS")):
        return
    rng = np.random.default_rng(0)
    schema = make_schema()
    all_rows = []
    for _ in range(shards * rows):
        n = int(rng.integers(4, MAX_LEN + 1))
        frames = rng.normal(size=(n, SEQ_DIM))
        label = int(frames[:, 0].mean() > 0)
        all_rows.append([label, [[float(x) for x in row] for row in frames]])
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.options import TFRecordOptions

    writer = DatasetWriter(
        data_dir,
        schema,
        TFRecordOptions.from_map(recordType="SequenceExample"),
        mode="overwrite",
        max_records_per_file=rows,
    )
    writer.write_rows(all_rows)


def main() -> None:
    data_dir = "/tmp/tpu_tfrecord_longdoc/data"
    ckpt_dir = "/tmp/tpu_tfrecord_longdoc/ckpt"
    generate(data_dir)
    schema = make_schema()

    # Pick (data, seq) such that the batch divides the data axis and the
    # padded length divides the seq axis — any device count works (odd
    # counts fall back to data=1).
    n_dev = len(jax.devices())
    for seq in (4, 2, 1):
        if n_dev % seq == 0 and BATCH % (n_dev // seq) == 0 and MAX_LEN % seq == 0:
            data = n_dev // seq
            break
    else:
        data, seq = 1, 1
    mesh = create_mesh({"data": data, "seq": seq}, jax.devices()[: data * seq])
    cfg = long_doc.LongDocConfig(
        seq_dim=SEQ_DIM, d_model=32, n_heads=4, n_layers=2, max_len=MAX_LEN,
        # 'ring' (default) or 'ulysses' — n_heads=4 covers every seq size
        # the picker above can choose, so both flavors run on any device
        # count (LONGDOC_SP_ATTENTION=ulysses to exercise the all-to-all SP)
        sp_attention=os.environ.get("LONGDOC_SP_ATTENTION", "ring"),
        # LONGDOC_MOE_EXPERTS=4 swaps the FFN for the Switch MoE layer
        moe_experts=int(os.environ.get("LONGDOC_MOE_EXPERTS", "0")),
        # LONGDOC_KV_HEADS=2 runs GQA (k/v carry fewer heads; with
        # sp_attention=ulysses it must divide the seq-axis size too)
        n_kv_heads=int(os.environ.get("LONGDOC_KV_HEADS", "0")),
    )
    params = long_doc.init_params(jax.random.key(0), cfg)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)
    step_fn = jax.jit(
        functools.partial(
            long_doc.train_step, cfg=cfg, tx=tx, mesh=mesh, data_axis="data"
        ),
        donate_argnums=(0, 1),
    )

    ds = TFRecordDataset(
        data_dir, batch_size=BATCH, schema=schema, num_epochs=2,
        recordType="SequenceExample", shuffle=True, seed=0,
    )
    import ml_dtypes

    from tpu_tfrecord.tpu import host_batch_from_columnar

    shardings = {}  # computed once; frames carries the (data, seq) spec

    def produce(cb):
        # pad + f32->bf16 fused in the native kernel: frames arrive in the
        # model's compute dtype at half the link bytes, with no host-side
        # f32 dense batch
        hb = host_batch_from_columnar(
            cb, ds.schema, pad_to={"frames": (MAX_LEN, SEQ_DIM)},
            cast={"frames": ml_dtypes.bfloat16},
        )
        hb.pop("frames_inner_len")
        if not shardings:
            shardings.update(long_doc.batch_shardings(mesh, hb))
        return make_global_batch(hb, mesh, shardings=shardings)

    def step(state, gb):
        params, opt_state = state
        params, opt_state, loss = step_fn(params, opt_state, gb)
        return (params, opt_state), loss

    # TFR_TRAIN_SPOOL_DIR spools this trainer (role=trainer) for the
    # fleet doctor; the step-phase recorder runs regardless
    spool = _harness.trainer_spool()
    phases = _harness.StepPhases()
    t0 = time.perf_counter()
    it, _resume = _harness.resume_or_fresh(ds, ckpt_dir)
    save_cb, saver = _harness.state_saver(ckpt_dir)
    try:
        with it:
            (params, opt_state), steps, duty = _harness.run_train_loop(
                it, produce, step, (params, opt_state),
                save=save_cb,
                phases=phases,
            )
        saver.wait()  # drain the background commit before the summary
        _harness.finish(ckpt_dir, steps, BATCH, t0, duty, phases=phases)
    finally:
        saver.close()
        _harness.release_trainer_spool(spool)


if __name__ == "__main__":
    main()
