"""Tests for the flagship DLRM consumer on the 8-device CPU mesh (dp x tp x sp
shardings compile and execute). The driver entry points it is reached
through are tests/test_graft_entry.py's."""

import dataclasses
import functools

import jax
import numpy as np
import optax
import pytest

from tpu_tfrecord.models import (
    DLRMConfig,
    forward,
    init_params,
    loss_fn,
    make_synthetic_batch,
    param_shardings,
    train_step,
)
from tpu_tfrecord.models.dlrm import (
    _gather_rows,
    batch_shardings,
    dense_rowwise_adagrad_reference,
)
from tpu_tfrecord.tpu.mesh import create_mesh


class TestDLRM:
    def test_forward_shapes_and_dtype(self):
        cfg = DLRMConfig(num_dense=4, num_categorical=3, vocab_size=16, embed_dim=4,
                         bottom_mlp=(8, 4), top_mlp=(8, 1))
        params = init_params(jax.random.key(0), cfg)
        batch = {k: jax.numpy.asarray(v) for k, v in make_synthetic_batch(cfg, 8).items()}
        logits = jax.jit(functools.partial(forward, cfg=cfg))(params, batch)
        assert logits.shape == (8,)
        assert logits.dtype == jax.numpy.float32

    def test_loss_decreases_under_training(self):
        cfg = DLRMConfig(num_dense=4, num_categorical=3, vocab_size=16, embed_dim=4,
                         bottom_mlp=(8, 4), top_mlp=(8, 1))
        params = init_params(jax.random.key(1), cfg)
        batch = {k: jax.numpy.asarray(v) for k, v in make_synthetic_batch(cfg, 32).items()}
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step = jax.jit(functools.partial(train_step, cfg=cfg, tx=tx))
        first = float(jax.jit(loss_fn, static_argnums=2)(params, batch, cfg))
        for _ in range(20):
            params, opt_state, loss = step(params, opt_state, batch)
        assert float(loss) < first

    def test_sequence_tower(self):
        cfg = DLRMConfig(num_dense=2, num_categorical=2, vocab_size=8, embed_dim=4,
                         bottom_mlp=(4,), top_mlp=(4, 1), seq_len=6, seq_dim=3)
        params = init_params(jax.random.key(2), cfg)
        batch = {k: jax.numpy.asarray(v) for k, v in make_synthetic_batch(cfg, 4).items()}
        fwd = jax.jit(functools.partial(forward, cfg=cfg))
        logits = fwd(params, batch)
        assert logits.shape == (4,)
        # padding must not influence the pooled sequence features
        b2 = dict(batch)
        frames = np.asarray(batch["frames"]).copy()
        lens = np.asarray(batch["frames_len"])
        for i, l in enumerate(lens):
            frames[i, l:] = 999.0  # garbage in padded region
        b2["frames"] = jax.numpy.asarray(frames)
        logits2 = fwd(params, b2)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2), rtol=2e-2)


def _lookup_case(dtype, interaction="dot"):
    cfg = DLRMConfig(num_dense=4, num_categorical=5, vocab_size=32, embed_dim=8,
                     bottom_mlp=(16, 8), top_mlp=(16, 1), interaction=interaction,
                     dtype=dtype)
    params = init_params(jax.random.key(7), cfg)
    batch = {k: jax.numpy.asarray(v) for k, v in make_synthetic_batch(cfg, 64, seed=3).items()}
    return cfg, params, batch


class TestEmbeddingLookup:
    """``forward`` reads the rows a batch names and rounds THEM, the lookup
    ``sparse_train_step`` differentiates through: no table-sized copy, and
    not one bit of a logit moved by it."""

    @pytest.mark.parametrize("dtype", [jax.numpy.bfloat16, jax.numpy.float32])
    @pytest.mark.parametrize("jit", [False, True])
    def test_rows_gathered_then_rounded_equal_a_rounded_table_gathered(self, dtype, jit):
        jnp = jax.numpy
        cfg, params, batch = _lookup_case(dtype)
        f_ix = jnp.arange(cfg.num_categorical)[None, :]

        def by_table(p, b):
            return forward(p, b, cfg)

        def by_rows(p, b):
            return forward(p, b, cfg, emb=p["embeddings"][f_ix, b["cat"]])

        def as_before_pr25(p, b):
            # the lookup forward had: the whole table cast to the activation
            # dtype, then take_along_axis over a [1, F, V, D] view of it
            table = p["embeddings"].astype(cfg.dtype)[None]
            emb = jnp.take_along_axis(table, b["cat"][:, :, None, None], axis=2)[:, :, 0, :]
            return forward(p, b, cfg, emb=emb)

        fns = [by_table, by_rows, as_before_pr25]
        if jit:
            fns = [jax.jit(f) for f in fns]
        got, rows, before = (np.asarray(f(params, batch)) for f in fns)
        assert np.isfinite(got).all() and np.abs(got).max() > 0
        np.testing.assert_array_equal(got, rows)
        np.testing.assert_array_equal(got, before)

    def test_an_index_outside_the_table_reads_the_same_row_scored_and_trained(self):
        """Past the end: the feature's LAST row (``take_along_axis`` filled
        NaN there before PR 25); negative: from the end. The same in
        ``forward`` and in the gather ``sparse_train_step`` trains through."""
        jnp = jax.numpy
        cfg, params, batch = _lookup_case(jnp.float32)
        v = cfg.vocab_size
        cat = np.asarray(batch["cat"]).copy()
        cat[0, :] = [v, v + 1000, -1, -3, 2**31 - 1]
        same = cat.copy()
        same[0, :] = [v - 1, v - 1, v - 1, v - 3, v - 1]
        rows = np.asarray(_gather_rows(params["embeddings"], jnp.asarray(cat)))
        table = np.asarray(params["embeddings"])
        np.testing.assert_array_equal(rows[0], table[np.arange(5), same[0]])
        np.testing.assert_array_equal(
            rows, np.asarray(_gather_rows(params["embeddings"], jnp.asarray(same))))
        fwd = jax.jit(functools.partial(forward, cfg=cfg))
        wild = np.asarray(fwd(params, dict(batch, cat=jnp.asarray(cat))))
        tame = np.asarray(fwd(params, dict(batch, cat=jnp.asarray(same))))
        assert np.isfinite(wild).all()
        np.testing.assert_array_equal(wild, tame)
        by_rows = jax.jit(lambda p, b, r: forward(p, b, cfg, emb=r))
        np.testing.assert_array_equal(
            wild, np.asarray(by_rows(params, batch, jnp.asarray(rows))))

    def test_the_table_gradient_is_the_scatter_of_the_row_gradients(self):
        """``train_step``'s dense path: the transpose of the pair gather adds
        each row's gradient into its table row, duplicates summed."""
        jnp = jax.numpy
        cfg, params, batch = _lookup_case(jnp.float32, interaction="cat")
        batch["cat"] = batch["cat"].at[1].set(batch["cat"][0])   # duplicates
        f_ix = jnp.arange(cfg.num_categorical)[None, :]
        g_table = jax.jit(jax.grad(loss_fn), static_argnums=2)(params, batch, cfg)["embeddings"]
        g_rows = jax.jit(jax.grad(lambda r: loss_fn(params, batch, cfg, emb=r)))(
            params["embeddings"][f_ix, batch["cat"]])
        want = jnp.zeros_like(params["embeddings"]).at[f_ix, batch["cat"]].add(g_rows)
        np.testing.assert_allclose(np.asarray(g_table), np.asarray(want), rtol=1e-6, atol=1e-9)
        assert float(jnp.abs(g_table).max()) > 0


class TestSparseTrainStep:
    """sparse_train_step: embedding grads via gathered rows + scatter-add
    (no dense [F, V, D] gradient), row-wise AdaGrad on touched rows."""

    CFG = DLRMConfig(num_dense=4, num_categorical=3, vocab_size=64, embed_dim=4,
                     bottom_mlp=(8, 4), top_mlp=(8, 1), dtype=jax.numpy.float32)
    #: rows by whether they fill whole 128-lane tiles: "wide" rows take the
    #: update's loop over blocks of B slots, "narrow" ones one block of N
    #: slots and no loop (dlrm._block_slots)
    ROWS = {"narrow_rows": CFG, "wide_rows": dataclasses.replace(CFG, embed_dim=128)}

    # the oracle (full dense table gradient + row-wise AdaGrad applied
    # densely) lives beside the step it checks; chip_smoke.py shares it.
    # One program here: bare, its backward runs primitive by primitive
    _dense_rowwise_adagrad_reference = staticmethod(
        jax.jit(dense_rowwise_adagrad_reference, static_argnums=(3, 4))
    )
    TX = optax.sgd(1e-2)  # one object: the jitted oracle is kept by it

    def _step_and_oracle(self, cfg, params, opt0, batch):
        """(the step's, the oracle's) params, state and loss; the step is
        traced anew (a case may patch the sort it takes)."""
        from tpu_tfrecord.models import sparse_train_step

        step = jax.jit(functools.partial(sparse_train_step, cfg=cfg, tx=self.TX))
        return step(params, opt0, batch), self._dense_rowwise_adagrad_reference(
            params, opt0, batch, cfg, self.TX)

    def test_matches_dense_reference_without_duplicates(self):
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step

        cfg = self.CFG
        params = init_params(jax.random.key(5), cfg)
        host = make_synthetic_batch(cfg, 8, seed=11)
        # force DISTINCT indices per feature column (duplicate handling is
        # pinned separately below)
        rng = np.random.default_rng(3)
        for f in range(cfg.num_categorical):
            host["cat"][:, f] = rng.choice(cfg.vocab_size, size=8, replace=False)
        batch = {k: jax.numpy.asarray(v) for k, v in host.items()}
        opt0 = sparse_opt_init(params, cfg, self.TX)

        (got_p, got_s, got_l), (want_p, want_s, want_l) = self._step_and_oracle(
            cfg, params, opt0, batch)
        assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
        np.testing.assert_allclose(got_s.accum, want_s.accum, rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(
            got_p["embeddings"], want_p["embeddings"], rtol=1e-5, atol=1e-7
        )
        for (ga, wa) in zip(jax.tree.leaves(got_p["top"]), jax.tree.leaves(want_p["top"])):
            np.testing.assert_allclose(ga, wa, rtol=1e-5, atol=1e-7)

    def test_duplicate_indices_accumulate_exactly(self):
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step

        cfg = self.CFG
        params = init_params(jax.random.key(6), cfg)
        host = make_synthetic_batch(cfg, 6, seed=13)
        host["cat"][:] = 7  # every example hits the SAME row of every table
        batch = {k: jax.numpy.asarray(v) for k, v in host.items()}
        tx = optax.sgd(1e-2)
        opt0 = sparse_opt_init(params, cfg, tx)
        embed_lr, embed_eps = 0.01, 1e-8

        got_p, got_s, _ = jax.jit(
            functools.partial(sparse_train_step, cfg=cfg, tx=tx,
                              embed_lr=embed_lr, embed_eps=embed_eps)
        )(params, opt0, batch)

        # DEDUP-FIRST oracle (r4, matches dense row-wise AdaGrad / TF
        # IndexedSlices consumers): duplicates sum their row gradients
        # FIRST; the accumulator adds mean((sum g)^2) ONCE per unique row;
        # the scale from the post-accumulation value applies to the summed
        # gradient. The dense table gradient row IS the summed gradient.
        grads = jax.jit(jax.grad(loss_fn), static_argnums=2)(params, batch, cfg)
        g_table = np.asarray(grads["embeddings"], dtype=np.float32)

        for f in range(cfg.num_categorical):
            want_acc = float((g_table[f, 7] ** 2).mean())
            assert float(got_s.accum[f, 7]) == pytest.approx(want_acc, rel=1e-5)
            scale = embed_lr / np.sqrt(want_acc + embed_eps)
            want_row = np.asarray(params["embeddings"])[f, 7] - scale * g_table[f, 7]
            np.testing.assert_allclose(got_p["embeddings"][f, 7], want_row,
                                       rtol=1e-4, atol=1e-7)
            # untouched rows unchanged
            np.testing.assert_array_equal(
                got_p["embeddings"][f, 8], np.asarray(params["embeddings"])[f, 8]
            )

    def test_mixed_duplicate_group_sizes_match_dense_oracle(self):
        # Group sizes m VARY within one batch (indices drawn from a tiny
        # range): a bug wrong only when different-sized duplicate groups
        # coexist (e.g. a scale paired with the wrong group's m) passes
        # both the no-duplicates and the all-duplicates cases — this pins
        # the realistic skewed-index regime. Dedup-first semantics make the
        # dense row-wise AdaGrad oracle exact for ANY index pattern.
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step

        cfg = self.CFG
        params = init_params(jax.random.key(9), cfg)
        host = make_synthetic_batch(cfg, 64, seed=21)
        host["cat"] = np.random.default_rng(23).integers(
            0, 6, size=host["cat"].shape
        )  # ~10x duplication, uneven group sizes
        batch = {k: jax.numpy.asarray(v) for k, v in host.items()}
        opt0 = sparse_opt_init(params, cfg, self.TX)
        (got_p, got_s, got_l), (want_p, want_s, want_l) = self._step_and_oracle(
            cfg, params, opt0, batch)
        assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
        np.testing.assert_allclose(got_s.accum, want_s.accum, rtol=2e-5, atol=1e-9)
        np.testing.assert_allclose(
            got_p["embeddings"], want_p["embeddings"], rtol=2e-5, atol=1e-7
        )

    def test_pair_sort_path_matches_flat_keys(self, monkeypatch):
        """ADVICE close-out: for F*V > 2^31, flat int32 dedup keys would
        silently wrap (int64 is unavailable with x64 disabled), so the
        step switches to a lexicographic (f, v) pair sort. Both paths are
        stable sorts over the same total order, so the permutation, each
        element's slot, the slots' keys — and therefore every update — are
        identical; pinned at test scale by shrinking the switch-over
        threshold."""
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step
        from tpu_tfrecord.models import dlrm as dlrm_mod

        # the sort seam itself, on skewed duplicate-heavy indices
        rng = np.random.default_rng(31)
        f_np = np.repeat(np.arange(3), 32).astype(np.int32)
        v_np = rng.integers(0, 6, 96).astype(np.int32)
        f_flat, v_flat = jax.numpy.asarray(f_np), jax.numpy.asarray(v_np)
        flat = [np.asarray(a) for a in
                dlrm_mod._dedup_sort(f_flat, v_flat, 6, force_pairs=False)]
        pairs = [np.asarray(a) for a in
                 dlrm_mod._dedup_sort(f_flat, v_flat, 6, force_pairs=True)]
        want_order = np.lexsort((v_np, f_np))        # stable, as both sorts are
        # the runs, columns in (f, v) order, and each sorted element's run
        runs, run_of = np.unique(
            np.stack([f_np, v_np])[:, want_order], axis=1, return_inverse=True)
        empty = 96 - runs.shape[1]
        for order, slot, uf, uv, n_runs in (flat, pairs):
            assert n_runs == runs.shape[1]
            np.testing.assert_array_equal(order, want_order)
            # the runs take the last slots in order; every slot before them
            # indexes no table, even after NumPy's wrap-around
            np.testing.assert_array_equal(slot, empty + run_of.reshape(-1))
            np.testing.assert_array_equal(uf[empty:], runs[0])
            np.testing.assert_array_equal(uv[empty:], runs[1])
            assert (uf[:empty] + 3 < 0).all()
        for got, want in zip(pairs[:2], flat[:2]):
            np.testing.assert_array_equal(got, want)

        # and the full step end-to-end with the pair path forced
        cfg = self.CFG
        params = init_params(jax.random.key(12), cfg)
        host = make_synthetic_batch(cfg, 32, seed=33)
        host["cat"] = rng.integers(0, 6, size=host["cat"].shape)
        batch = {k: jax.numpy.asarray(v) for k, v in host.items()}
        tx = optax.sgd(1e-2)
        opt0 = sparse_opt_init(params, cfg, tx)
        step = functools.partial(sparse_train_step, cfg=cfg, tx=tx)
        want_p, want_s, want_l = jax.jit(step)(params, opt0, batch)
        monkeypatch.setattr(dlrm_mod, "_FLAT_KEY_MAX", 1)
        got_p, got_s, got_l = jax.jit(step)(params, opt0, batch)
        assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
        np.testing.assert_array_equal(
            np.asarray(got_s.accum), np.asarray(want_s.accum)
        )
        np.testing.assert_array_equal(
            np.asarray(got_p["embeddings"]), np.asarray(want_p["embeddings"])
        )

    #: distinct keys a column (R = their sum) for the loop's edges at
    #: B = 48, F = 3: the update walks blocks of B slots from the back
    _RUNS_A_COLUMN = {
        "runs_fill_one_block": (16, 16, 16),
        "runs_fill_two_blocks": (32, 32, 32),
        "one_run_past_a_block": (16, 16, 17),   # one real key behind B - 1 empty slots
    }
    #: case -> (R, blocks of B = 48 slots the update walks)
    _KEYS_CASES = {
        "every_key_distinct": (144, 3),
        "one_key_a_column": (3, 1),
        "extreme_keys": (None, None),
        "heavy_duplication": (9, 1),
        "runs_fill_one_block": (48, 1),
        "runs_fill_two_blocks": (96, 2),
        "one_run_past_a_block": (49, 2),
    }

    @classmethod
    def _keys_case(cls, case: str, cfg, batch_size: int) -> np.ndarray:
        """[B, F] keys for the ends of the run compaction and its edges."""
        n_f, n_v = cfg.num_categorical, cfg.vocab_size
        rng = np.random.default_rng(41)
        if case == "every_key_distinct":            # N runs: no empty slot
            return np.stack(
                [rng.choice(n_v, size=batch_size, replace=False) for _ in range(n_f)], axis=1)
        if case == "one_key_a_column":              # F runs: all but F slots empty
            return np.broadcast_to(rng.integers(0, n_v, size=n_f), (batch_size, n_f)).copy()
        if case == "extreme_keys":      # row 0 of feature 0, row V-1 of feature F-1: real keys
            cat = rng.integers(1, 6, size=(batch_size, n_f))
            cat[::3, n_f - 1] = n_v - 1
            cat[1::4, 0] = 0
            cat[1, 0] = n_v - 1
            return cat
        if case in cls._RUNS_A_COLUMN:              # every chosen key is drawn at least once
            return np.stack(
                [rng.permutation(np.resize(rng.choice(n_v, size=c, replace=False), batch_size))
                 for c in cls._RUNS_A_COLUMN[case]], axis=1)
        assert case == "heavy_duplication"
        return rng.integers(0, 3, size=(batch_size, n_f))

    @pytest.mark.parametrize("rows", list(ROWS))
    @pytest.mark.parametrize("sort_path", ["flat_keys", "pair_sort"])
    @pytest.mark.parametrize("case", list(_KEYS_CASES))
    def test_runs_compacted_match_dense_oracle(self, case, sort_path, rows, monkeypatch):
        """What exists once per unique row is kept once per run, at the
        run's slot, and the empty slots carry keys that index no table. The
        two ends (no empty slot: every block walked; all but F empty: one
        block), the loop's edges between them (the runs fill one block, two
        blocks, one block and one slot of the next), the least and the
        largest real key (neither may be taken for an empty slot's), and
        heavy duplication, against the dense oracle; rows and accumulators
        no key names equal the old ones bit for bit. With wide rows through
        the loop, with narrow rows through one block of all N slots."""
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step
        from tpu_tfrecord.models import dlrm as dlrm_mod

        if sort_path == "pair_sort":
            monkeypatch.setattr(dlrm_mod, "_FLAT_KEY_MAX", 1)
        cfg = self.ROWS[rows]
        params = init_params(jax.random.key(14), cfg)
        host = make_synthetic_batch(cfg, 48, seed=43)
        host["cat"] = self._keys_case(case, cfg, 48)
        batch = {k: jax.numpy.asarray(v) for k, v in host.items()}
        opt0 = sparse_opt_init(params, cfg, self.TX)
        # a state that has been trained on: an untouched accumulator is not zero
        opt0 = opt0._replace(accum=jax.random.uniform(
            jax.random.key(15), opt0.accum.shape, jax.numpy.float32, 0.0, 1e-6))
        (got_p, got_s, got_l), (want_p, want_s, want_l) = self._step_and_oracle(
            cfg, params, opt0, batch)
        assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
        np.testing.assert_allclose(got_s.accum, want_s.accum, rtol=2e-5, atol=1e-9)
        np.testing.assert_allclose(
            got_p["embeddings"], want_p["embeddings"], rtol=2e-5, atol=1e-7
        )
        touched = np.zeros((cfg.num_categorical, cfg.vocab_size), bool)
        touched[np.arange(cfg.num_categorical)[None, :], host["cat"]] = True
        n_runs, _ = self._KEYS_CASES[case]
        if case == "extreme_keys":
            assert touched[0, 0] and touched[-1, -1]
        else:
            assert touched.sum() == n_runs
        moved = np.asarray(got_s.accum) != np.asarray(opt0.accum)
        np.testing.assert_array_equal(moved, touched)
        np.testing.assert_array_equal(
            np.asarray(got_p["embeddings"])[~touched],
            np.asarray(params["embeddings"])[~touched])
        assert (np.asarray(got_p["embeddings"])[touched]
                != np.asarray(params["embeddings"])[touched]).any(axis=-1).all()

    @pytest.mark.parametrize(
        "case", [c for c, (n_runs, _) in _KEYS_CASES.items() if n_runs is not None])
    def test_the_update_walks_only_the_blocks_that_hold_runs(self, case, monkeypatch):
        """The loop over the table's update (the accumulators read back, the
        scale, the row scatter) takes blocks of B slots (the batch's rows: F
        of them make N) and as many as hold runs, ceil(R / B): F for a batch
        of distinct keys, one when the runs just fill a block, two for one
        run more."""
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step
        from tpu_tfrecord.models import dlrm as dlrm_mod

        cfg, batch_size = self.ROWS["wide_rows"], 48
        n_runs, n_blocks = self._KEYS_CASES[case]
        host = make_synthetic_batch(cfg, batch_size, seed=43)
        host["cat"] = self._keys_case(case, cfg, batch_size)
        f_flat = jax.numpy.tile(jax.numpy.arange(cfg.num_categorical), batch_size)
        got_runs = dlrm_mod._dedup_sort(
            f_flat, jax.numpy.asarray(host["cat"]).reshape(-1), cfg.vocab_size)[-1]
        assert int(got_runs) == n_runs
        assert int(dlrm_mod._blocks_with_runs(got_runs, batch_size)) == n_blocks
        assert n_blocks == -(-n_runs // batch_size)

        asked = []
        helper = dlrm_mod._blocks_with_runs

        def recording(n_runs, block):
            asked.append(block)
            return helper(n_runs, block)

        monkeypatch.setattr(dlrm_mod, "_blocks_with_runs", recording)
        params = init_params(jax.random.key(14), cfg)
        tx = optax.sgd(1e-2)
        jax.make_jaxpr(functools.partial(sparse_train_step, cfg=cfg, tx=tx))(
            params, sparse_opt_init(params, cfg, tx),
            {k: jax.numpy.asarray(v) for k, v in host.items()})
        assert asked == [batch_size]        # the step's trip count is the helper's

    def test_a_block_is_the_batch_where_rows_fill_lane_tiles(self):
        """The block is a function of the step's shapes alone: B slots where
        a row is whole 128-lane tiles (the table lies row-major on a TPU and
        the loop carries it in place), all N slots otherwise (the compiler
        relayouts a table of narrower rows around every scatter call)."""
        from tpu_tfrecord.models import dlrm as dlrm_mod

        assert dlrm_mod._block_slots(16384, 16384 * 26, 128) == 16384
        assert dlrm_mod._block_slots(48, 144, 256) == 48
        for d in (4, 16, 32, 64, 96, 192):
            assert dlrm_mod._block_slots(16384, 16384 * 26, d) == 16384 * 26

    def test_an_index_outside_the_table_trains_the_row_it_reads(self):
        """The dedup keys are built from the indices, so an index outside
        [0, V) is folded first, as the lookup folds it: past the end the
        feature's last row, negative from the end. The step is the step of
        the folded batch, bit for bit."""
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step

        cfg = self.CFG
        v = cfg.vocab_size
        params = init_params(jax.random.key(16), cfg)
        host = make_synthetic_batch(cfg, 16, seed=45)
        wild, tame = host["cat"].copy(), host["cat"].copy()
        wild[0, :] = [v, -1, 2**31 - 1]
        tame[0, :] = [v - 1, v - 1, v - 1]
        wild[1, :] = [-3, v + 1000, -v - 7]
        tame[1, :] = [v - 3, v - 1, 0]
        tx = optax.sgd(1e-2)
        opt0 = sparse_opt_init(params, cfg, tx)
        step = jax.jit(functools.partial(sparse_train_step, cfg=cfg, tx=tx))
        got_p, got_s, got_l = step(
            params, opt0, {k: jax.numpy.asarray(a) for k, a in dict(host, cat=wild).items()})
        want_p, want_s, want_l = step(
            params, opt0, {k: jax.numpy.asarray(a) for k, a in dict(host, cat=tame).items()})
        assert float(got_l) == float(want_l)
        np.testing.assert_array_equal(np.asarray(got_s.accum), np.asarray(want_s.accum))
        np.testing.assert_array_equal(
            np.asarray(got_p["embeddings"]), np.asarray(want_p["embeddings"]))
        assert float(got_s.accum[0, v - 1]) > 0 and float(got_s.accum[2, 0]) > 0

    @pytest.mark.parametrize("rows", list(ROWS))
    @pytest.mark.parametrize("sort_path", ["flat_keys", "pair_sort"])
    def test_the_step_gathers_and_scatters_only_what_the_update_needs(
        self, sort_path, rows, monkeypatch
    ):
        """The passes over the batch's N keys that only moved bookkeeping
        stay deleted: the step's jaxpr holds three gathers (the lookup, the
        row gradients into sorted order, the accumulators read back) and
        three scatter-adds (the segment sum, the accumulator, the table;
        with wide rows the read-back and the table's scatter in the body of
        the one loop over the blocks of slots that hold runs, with narrow
        rows no loop),
        and no gather reads an [N]-long array (the keys come out of sorts;
        run lengths and per-element views of the run sums are gone)."""
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step
        from tpu_tfrecord.models import dlrm as dlrm_mod

        if sort_path == "pair_sort":
            monkeypatch.setattr(dlrm_mod, "_FLAT_KEY_MAX", 1)
        cfg = self.ROWS[rows]
        params = init_params(jax.random.key(17), cfg)
        batch = {k: jax.numpy.asarray(a) for k, a in make_synthetic_batch(cfg, 40, seed=47).items()}
        tx = optax.sgd(1e-2)
        opt0 = sparse_opt_init(params, cfg, tx)
        n, d = 40 * cfg.num_categorical, cfg.embed_dim
        jaxpr = jax.make_jaxpr(functools.partial(sparse_train_step, cfg=cfg, tx=tx))(
            params, opt0, batch)

        def equations(jp):
            for eqn in jp.eqns:
                yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from equations(sub)

        eqns = list(equations(jaxpr.jaxpr))
        gathers = [e.invars[0].aval.shape for e in eqns if e.primitive.name == "gather"]
        scatters = [e.invars[0].aval.shape for e in eqns if e.primitive.name == "scatter-add"]
        table, accum = params["embeddings"].shape, opt0.accum.shape
        assert sorted(gathers) == sorted([table, (n, d), accum])
        assert sorted(scatters) == sorted([(n, d), accum, table])
        assert not [e for e in eqns if e.primitive.name == "scatter"]
        assert sum(e.primitive.name == "sort" for e in eqns) == 2
        assert sum(e.primitive.name == "while" for e in eqns) == (rows == "wide_rows")

    @pytest.mark.parametrize("rows", list(ROWS))
    def test_sharded_sparse_step_matches_single_device(self, rows):
        from tpu_tfrecord.models import sparse_opt_init, sparse_train_step
        from tpu_tfrecord.models.dlrm import batch_shardings

        cfg = self.ROWS[rows]
        params = init_params(jax.random.key(8), cfg)
        host = make_synthetic_batch(cfg, 16, seed=17)
        batch1 = {k: jax.numpy.asarray(v) for k, v in host.items()}
        tx = optax.sgd(1e-2)
        opt0 = sparse_opt_init(params, cfg, tx)
        want_p, _, want_l = jax.jit(
            functools.partial(sparse_train_step, cfg=cfg, tx=tx)
        )(params, opt0, batch1)

        mesh = create_mesh({"data": 4, "model": 2})
        p_shard = param_shardings(mesh, params)
        sharded_params = jax.device_put(params, p_shard)
        b_shard = batch_shardings(mesh, host)
        batch = {
            k: jax.make_array_from_process_local_data(b_shard[k], v)
            for k, v in host.items()
        }
        got_p, _, got_l = jax.jit(
            functools.partial(sparse_train_step, cfg=cfg, tx=tx)
        )(sharded_params, opt0, batch)
        assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
        np.testing.assert_allclose(
            np.asarray(got_p["embeddings"]), np.asarray(want_p["embeddings"]),
            rtol=1e-5, atol=1e-7,
        )


class TestShardedTrainStep:
    def test_tp_matches_replicated(self):
        """The tensor-parallel layout must compute the same loss as fully
        replicated params (collectives are inserted, not semantics changed)."""
        cfg = DLRMConfig(num_dense=4, num_categorical=3, vocab_size=16, embed_dim=4,
                         bottom_mlp=(8, 4), top_mlp=(8, 1))
        params = init_params(jax.random.key(3), cfg)
        host = make_synthetic_batch(cfg, 16, seed=7)

        # replicated single-device loss
        batch1 = {k: jax.numpy.asarray(v) for k, v in host.items()}
        want = float(loss_fn(params, batch1, cfg))

        mesh = create_mesh({"data": 4, "model": 2})
        p_shard = param_shardings(mesh, params)
        sharded_params = jax.device_put(params, p_shard)
        b_shard = batch_shardings(mesh, host)
        batch = {
            k: jax.make_array_from_process_local_data(b_shard[k], v)
            for k, v in host.items()
        }
        got = float(jax.jit(functools.partial(loss_fn, cfg=cfg))(sharded_params, batch))
        assert got == pytest.approx(want, rel=2e-2)  # bf16 tolerance
