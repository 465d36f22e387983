#!/usr/bin/env python
"""End-to-end example: train the DLRM consumer on TFRecord data.

Covers the whole framework surface:
  1. generate a Criteo-like TFRecord dataset (columnar native encode)
  2. stream it with TFRecordDataset (native decode, prefetch, shuffle)
  3. hash categoricals, pack columns, assemble global sharded batches
  4. jit train steps over the mesh; checkpoint the input position
  5. resume from the saved state

Run on any JAX backend; for a local simulation:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_dlrm.py
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
from criteo import CAT_COLS, DENSE_COLS, NUM_CAT, NUM_DENSE, criteo_schema, write_dataset

from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.models import DLRMConfig, init_params, train_step
from tpu_tfrecord.tpu import create_mesh, host_batch_from_columnar, make_global_batch

VOCAB = 1 << 16
BATCH = 1024


def main() -> None:
    data_dir = "/tmp/tpu_tfrecord_example/data"
    ckpt_dir = "/tmp/tpu_tfrecord_example/ckpt"
    # the files examples/criteo_prepare.py writes from a real TSV are read
    # the same way: one schema (examples/criteo.py). Kept across runs, so a
    # rerun resumes against the same dataset fingerprint.
    if not os.path.exists(os.path.join(data_dir, "_SUCCESS")):
        write_dataset(data_dir, seed=0, shards=4, rows_per_shard=4096)
    schema = criteo_schema()

    mesh = create_mesh()
    cfg = DLRMConfig(
        num_dense=NUM_DENSE, num_categorical=NUM_CAT, vocab_size=VOCAB, embed_dim=16
    )
    params = init_params(jax.random.key(0), cfg)
    tx = optax.adam(1e-3)
    if os.environ.get("DLRM_SPARSE", "0") == "1":
        # Sparse embedding updates (row-wise AdaGrad on touched rows only):
        # the table gradient never materializes, which is what makes real
        # Criteo vocabularies (2^20+ rows/table) trainable — see
        # models.dlrm.sparse_train_step. Adam still drives the MLPs.
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step

        opt_state = sparse_opt_init(params, cfg, tx)
        step_fn = jax.jit(
            functools.partial(sparse_train_step, cfg=cfg, tx=tx), donate_argnums=(0, 1)
        )
    else:
        opt_state = tx.init(params)
        step_fn = jax.jit(functools.partial(train_step, cfg=cfg, tx=tx), donate_argnums=(0, 1))

    hash_buckets = {c: VOCAB for c in CAT_COLS}
    pack = {"dense": DENSE_COLS, "cat": CAT_COLS}

    # NOTE: in a real job the input state is saved/restored TOGETHER with the
    # model checkpoint (params/opt_state) at the same step — here only the
    # input position is persisted, to keep the example focused on the data
    # pipeline (train_lm.py shows the atomic combined checkpoint).
    ds = TFRecordDataset(
        data_dir, batch_size=BATCH, schema=schema, num_epochs=2,
        # two-scale mixing: seeded shard-order shuffle + windowed row
        # shuffle (rows permute across 8-batch windows; resume-exact)
        shuffle=True, shuffle_window=8, seed=0
    )

    def produce(cb):
        hb = host_batch_from_columnar(
            cb, ds.schema, hash_buckets=hash_buckets, pack=pack
        )
        # standard Criteo dense preprocessing: log(1+x)
        hb["dense"] = np.log1p(hb["dense"].clip(min=0)).astype(np.float32)
        hb["label"] = hb["label"].astype(np.float32)
        return make_global_batch(hb, mesh)

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
        _harness.finish(
            ckpt_dir, steps, BATCH, t0, duty, stages=True, phases=phases
        )
    finally:
        saver.close()
        _harness.release_trainer_spool(spool)


if __name__ == "__main__":
    main()
