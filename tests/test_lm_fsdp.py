"""Full GSPMD mesh (PR 19): dp×fsdp×pp(+EP) weight sharding and
segment-masked bin packing. Pins: every fsdp composition reproduces the
pure-dp loss trajectory on the same params and data; per-device param
bytes shrink ~linearly in the fsdp axis; checkpoints move freely between
mesh layouts through `AsyncCheckpointer`; packed rows score exactly like
each document alone (the per-document oracle); and the TokenPacker bin
modes checkpoint/resume byte-identically."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tools.graftlint import hlo_contracts
from tpu_tfrecord.checkpoint import AsyncCheckpointer
from tpu_tfrecord.models import lm
from tpu_tfrecord.tpu import TokenPacker, create_mesh

CFG = lm.LMConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=2, max_len=16)
CFG4 = lm.LMConfig(
    vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16, n_micro=4
)
_PLACEMENT_AXES = ("pipe_axis", "expert_axis", "fsdp_axis")


#: the dense references, one program a configuration and shape (bare, they
#: run primitive by primitive, each primitive a compile)
dense_forward = jax.jit(lm.forward, static_argnums=2)
dense_loss = jax.jit(lm.loss_fn, static_argnums=2)


def batch(cfg=CFG, b=8, seed=0):
    return jnp.asarray(lm.make_synthetic_tokens(cfg, b, seed=seed))


def place(params, mesh, **axes):
    return jax.device_put(params, lm.param_shardings(mesh, params, **axes))


TX = optax.adam(3e-3)


@functools.cache
def train_step(cfg, mesh=None, **axes):
    """The jitted ``lm.train_step`` of a configuration, mesh and layout, one
    for the module: a trajectory and the run it is compared with, in one
    test or in two, share the program."""
    return jax.jit(
        functools.partial(lm.train_step, cfg=cfg, tx=TX, mesh=mesh, **axes)
    )


def trajectory(cfg, mesh=None, steps=6, **axes):
    params = lm.init_params(jax.random.key(0), cfg)
    if mesh is not None:
        pl = {k: axes[k] for k in _PLACEMENT_AXES if axes.get(k)}
        params = place(params, mesh, **pl)
    opt = TX.init(params)
    step = train_step(cfg, mesh, **axes)
    losses = []
    for i in range(steps):
        params, opt, loss = step(params, opt, batch(cfg, b=8, seed=100 + i))
        losses.append(float(loss))
    return losses


class TestFSDPTrajectory:
    """Weight sharding must be a LAYOUT choice, not a numerics choice:
    same params + same data => the pure-dp loss trajectory."""

    def test_dp_fsdp_matches_pure_dp(self):
        ref = trajectory(CFG)
        mesh = create_mesh({"data": 2, "fsdp": 4})
        got = trajectory(CFG, mesh=mesh, data_axis="data", fsdp_axis="fsdp")
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)

    def test_dp_fsdp_pp_matches_pure_dp(self):
        """The full 3-axis mesh: params at rest P(pipe, fsdp, ...), the
        pipeline's own param_spec boundary reshard does the per-step
        gather — zero pipeline.py changes, same trajectory."""
        ref = trajectory(CFG4)
        mesh = create_mesh({"pipe": 2, "data": 2, "fsdp": 2})
        got = trajectory(
            CFG4, mesh=mesh, data_axis="data", pipe_axis="pipe",
            fsdp_axis="fsdp",
        )
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)

    def test_dp_fsdp_ep_matches_dp_ep(self):
        """fsdp composed against the expert axis: the moe shard_map's
        in_spec reshard gathers ONLY the fsdp dim, so adding fsdp to
        dp×ep must not move the trajectory at all. (EP itself diverges
        from pure dp by routing/capacity discreteness — pre-existing —
        so the tight pin is against dp×ep on the SAME mesh, with a
        coarse sanity bound against pure dp.)"""
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, max_len=16,
            moe_experts=4, moe_top_k=2, moe_capacity_factor=4.0,
        )
        mesh = create_mesh({"data": 2, "fsdp": 2, "expert": 2})
        ref_ep = trajectory(
            cfg, mesh=mesh, data_axis="data", expert_axis="expert"
        )
        got = trajectory(
            cfg, mesh=mesh, data_axis="data", expert_axis="expert",
            fsdp_axis="fsdp",
        )
        np.testing.assert_allclose(got, ref_ep, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got, trajectory(cfg), atol=0.05)


class TestFSDPMemory:
    """The point of fsdp: per-device at-rest bytes (params + opt state,
    the compiled argument bytes) shrink ~linearly in the fsdp axis."""

    def _argument_bytes(self, mesh_axes, fsdp_axis):
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16
        )
        mesh = create_mesh(mesh_axes)
        params = lm.init_params(jax.random.key(0), cfg)
        params = place(params, mesh, fsdp_axis=fsdp_axis)
        opt = TX.init(params)
        toks = jax.device_put(
            batch(cfg), NamedSharding(mesh, P("data", None))
        )
        step = train_step(cfg, mesh, data_axis="data", fsdp_axis=fsdp_axis)
        mem = step.lower(params, opt, toks).compile().memory_analysis()
        return mem.argument_size_in_bytes

    def test_param_bytes_shrink_linearly_in_fsdp(self):
        b1 = self._argument_bytes({"data": 8}, None)
        b2 = self._argument_bytes({"data": 4, "fsdp": 2}, "fsdp")
        b4 = self._argument_bytes({"data": 2, "fsdp": 4}, "fsdp")
        # ~linear: each doubling of fsdp roughly halves the at-rest
        # bytes (0.65 leaves room for the unsharded scalars/biases and
        # the replicated token batch)
        assert b2 < 0.65 * b1, (b1, b2)
        assert b4 < 0.65 * b2, (b2, b4)


class TestFSDPContracts:
    def test_dp_fsdp_hlo_contract(self):
        hlo_contracts.verify("lm_train_step_fsdp")

    def test_dp_fsdp_pp_hlo_contract(self):
        hlo_contracts.verify("lm_train_step_fsdp_pp")


class TestCheckpointInterchange:
    """A checkpoint is layout-free: save under pure dp, restore under
    dp×fsdp or dp×fsdp×pp (and back) — params byte-identical through the
    round trip, trajectories indistinguishable at test scale."""

    def _host(self, tree):
        return jax.tree.map(np.asarray, jax.device_get(tree))

    def _place_state(self, mesh, params, opt, **axes):
        p_sh = place(params, mesh, **axes)
        tmpl = TX.init(p_sh)  # zeros_like: inherits the sharded layout
        repl = NamedSharding(mesh, P())

        def put(t, v):
            sh = t.sharding if isinstance(t.sharding, NamedSharding) else repl
            return jax.device_put(jnp.asarray(v), sh)

        opt_sh = jax.tree.map(put, tmpl, opt)
        return p_sh, opt_sh

    def _run(self, cfg, params, opt, mesh, steps, seed0, **axes):
        step = train_step(cfg, mesh, **axes)
        losses = []
        for i in range(steps):
            params, opt, loss = step(
                params, opt, batch(cfg, b=8, seed=seed0 + i)
            )
            losses.append(float(loss))
        return params, opt, losses

    def test_save_dp_restore_fsdp_and_fsdp_pp(self, tmp_path):
        cfg = CFG4
        ref = trajectory(cfg)
        params = lm.init_params(jax.random.key(0), cfg)
        opt = TX.init(params)
        params, opt, head = self._run(cfg, params, opt, None, 3, 100)
        np.testing.assert_allclose(head, ref[:3], rtol=1e-6)
        saved_host = self._host({"params": params, "opt": opt})
        with AsyncCheckpointer(str(tmp_path / "dp")) as ckpt:
            ckpt.save(3, {"params": params, "opt": opt})
            ckpt.wait()
            fresh = lm.init_params(jax.random.key(1), cfg)
            step_no, state, _ = ckpt.restore(
                {"params": fresh, "opt": TX.init(fresh)}
            )
        assert step_no == 3
        jax.tree.map(
            np.testing.assert_array_equal, state, saved_host
        )  # save/restore is byte-identical
        for mesh_axes, axes in (
            ({"data": 2, "fsdp": 4},
             dict(data_axis="data", fsdp_axis="fsdp")),
            ({"pipe": 2, "data": 2, "fsdp": 2},
             dict(data_axis="data", pipe_axis="pipe", fsdp_axis="fsdp")),
        ):
            mesh = create_mesh(mesh_axes)
            pl = {k: axes[k] for k in _PLACEMENT_AXES if axes.get(k)}
            p_sh, opt_sh = self._place_state(
                mesh, state["params"], state["opt"], **pl
            )
            _, _, tail = self._run(
                cfg, p_sh, opt_sh, mesh, 3, 103, **axes
            )
            np.testing.assert_allclose(tail, ref[3:], rtol=1e-5, atol=1e-6)

    def test_save_fsdp_restore_dp(self, tmp_path):
        cfg = CFG4
        mesh = create_mesh({"data": 2, "fsdp": 4})
        axes = dict(data_axis="data", fsdp_axis="fsdp")
        full = trajectory(cfg, mesh=mesh, **axes)
        params = place(lm.init_params(jax.random.key(0), cfg), mesh,
                       fsdp_axis="fsdp")
        opt = TX.init(params)
        params, opt, head = self._run(cfg, params, opt, mesh, 3, 100, **axes)
        np.testing.assert_allclose(head, full[:3], rtol=1e-6)
        saved_host = self._host({"params": params, "opt": opt})
        with AsyncCheckpointer(str(tmp_path / "fsdp")) as ckpt:
            ckpt.save(3, {"params": params, "opt": opt})
            ckpt.wait()
            fresh = lm.init_params(jax.random.key(1), cfg)
            _, state, _ = ckpt.restore(
                {"params": fresh, "opt": TX.init(fresh)}
            )
        jax.tree.map(np.testing.assert_array_equal, state, saved_host)
        _, _, tail = self._run(
            cfg, state["params"], state["opt"], None, 3, 103
        )
        np.testing.assert_allclose(tail, full[3:], rtol=1e-5, atol=1e-6)


def _pack_batch(docs, b=2, seq_len=16, packing="best_fit"):
    packer = TokenPacker(b, seq_len, packing=packing)
    packer.feed_docs(docs)
    out = packer.pop()
    assert out is not None, "corpus did not close a batch"
    return out["tokens"], out["segment_ids"]


def _oracle_docs(rng, sizes):
    return [rng.integers(1, CFG.vocab_size, size=s).astype(np.int32)
            for s in sizes]


class TestSegmentOracle:
    """Segment-masked packing vs the per-document oracle: a packed row
    must produce, at each document's positions, exactly the logits of
    that document run alone — same mask, same (per-segment) positions."""

    def _alone(self, toks, segs, r, s):
        """Extract doc (row r, segment s) into its own single-doc row."""
        pos = np.where(segs[r] == s)[0]
        at, n = int(pos[0]), int(pos.size)
        cap = toks.shape[1]
        a_toks = np.zeros((1, cap), np.int32)
        a_toks[0, :n] = toks[r, at : at + n]
        a_segs = np.zeros((1, cap), np.int32)
        a_segs[0, :n] = 1
        return a_toks, a_segs, at, n

    def test_packed_logits_match_per_document_oracle(self):
        rng = np.random.default_rng(7)
        # 9/6/12 (+eos) fill two rows of cap 17; the trailing 4-doc fits
        # no open bin and closes the batch
        toks, segs = _pack_batch(_oracle_docs(rng, [9, 6, 12, 4]))
        params = lm.init_params(jax.random.key(0), CFG)
        packed, _ = dense_forward(params, jnp.asarray(toks), CFG,
                                  segments=jnp.asarray(segs))
        packed = np.asarray(packed)
        L = packed.shape[1]
        checked = 0
        for r in range(toks.shape[0]):
            for s in np.unique(segs[r][segs[r] > 0]):
                a_toks, a_segs, at, n = self._alone(toks, segs, r, s)
                alone, _ = dense_forward(
                    params, jnp.asarray(a_toks), CFG,
                    segments=jnp.asarray(a_segs),
                )
                m = min(at + n, L) - at
                np.testing.assert_allclose(
                    packed[r, at : at + m], np.asarray(alone)[0, :m],
                    rtol=1e-5, atol=1e-5,
                )
                checked += 1
        assert checked == 3

    def test_packed_masked_loss_is_per_document_mean(self):
        """The segment-masked CE is exactly the valid-position-weighted
        mean of each document's alone CE: no cross-document targets, no
        pad contribution."""
        rng = np.random.default_rng(7)
        toks, segs = _pack_batch(_oracle_docs(rng, [9, 6, 12, 4]))
        params = lm.init_params(jax.random.key(0), CFG)
        packed = float(dense_loss(params, jnp.asarray(toks), CFG,
                                  segments=jnp.asarray(segs)))
        num = den = 0.0
        for r in range(toks.shape[0]):
            for s in np.unique(segs[r][segs[r] > 0]):
                a_toks, a_segs, _, n = TestSegmentOracle._alone(
                    self, toks, segs, r, s
                )
                l_d = float(dense_loss(params, jnp.asarray(a_toks), CFG,
                                       segments=jnp.asarray(a_segs)))
                num += l_d * (n - 1)
                den += n - 1
        np.testing.assert_allclose(packed, num / den, rtol=1e-5)

    def test_sp_fsdp_segments_forward_matches_dense(self):
        """Tentpole composition: segment masking through the zigzag ring
        (sp) UNDER fsdp weight sharding == the dense reference."""
        rng = np.random.default_rng(11)
        docs = [rng.integers(1, CFG.vocab_size, size=int(n)).astype(np.int32)
                for n in rng.integers(3, 15, size=60)]
        toks, segs = _pack_batch(docs, b=8)
        params = lm.init_params(jax.random.key(0), CFG)
        want, _ = dense_forward(params, jnp.asarray(toks), CFG,
                                segments=jnp.asarray(segs))
        mesh = create_mesh({"data": 2, "seq": 2, "fsdp": 2})
        p_sh = place(params, mesh, fsdp_axis="fsdp")
        got, _ = jax.jit(
            functools.partial(
                lm.forward, cfg=CFG, mesh=mesh, data_axis="data",
                seq_axis="seq", fsdp_axis="fsdp",
            )
        )(p_sh, jnp.asarray(toks), segments=jnp.asarray(segs))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_train_with_segments_dp_fsdp_matches_dense(self):
        """End to end: best-fit packed batches + segment-masked loss
        train identically dense vs dp×fsdp, and the loss actually
        falls."""
        rng = np.random.default_rng(3)
        packer = TokenPacker(8, CFG.max_len, packing="best_fit")
        packer.feed_docs(
            rng.integers(1, CFG.vocab_size, size=int(n)).astype(np.int32)
            for n in rng.integers(3, 15, size=400)
        )
        batches = []
        while len(batches) < 6:
            out = packer.pop()
            assert out is not None
            batches.append(out)

        def run(mesh, **axes):
            params = lm.init_params(jax.random.key(0), CFG)
            if mesh is not None:
                params = place(params, mesh, fsdp_axis=axes["fsdp_axis"])
            opt = TX.init(params)
            step = train_step(CFG, mesh, **axes)  # traced anew with segments
            losses = []
            for hb in batches:
                params, opt, loss = step(
                    params, opt, jnp.asarray(hb["tokens"]),
                    segments=jnp.asarray(hb["segment_ids"]),
                )
                losses.append(float(loss))
            return losses

        ref = run(None)
        mesh = create_mesh({"data": 2, "fsdp": 4})
        got = run(mesh, data_axis="data", fsdp_axis="fsdp")
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)
        assert ref[-1] < ref[0]

    def test_segments_rejected_in_pipeline(self):
        mesh = create_mesh({"pipe": 2, "data": 4})
        params = lm.init_params(jax.random.key(0), CFG4)
        toks = batch(CFG4)
        segs = jnp.ones_like(toks)
        with pytest.raises(ValueError, match="pipeline"):
            lm.forward(params, toks, CFG4, mesh, pipe_axis="pipe",
                       segments=segs)


class TestTokenPackerBins:
    """Satellite 3: best-fit bin packing — exact placement, byte-identical
    mid-carry resume, and density >= the greedy (first-fit) baseline."""

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="packing"):
            TokenPacker(2, 8, packing="nope")

    def test_best_fit_placement_and_segments(self):
        packer = TokenPacker(2, 8, packing="best_fit")  # cap 9
        d5 = np.arange(1, 6, dtype=np.int32)
        d3 = np.arange(11, 14, dtype=np.int32)
        d7 = np.arange(21, 28, dtype=np.int32)
        packer.feed_docs([d5, d3, d7])  # +eos: 6, 4, 8 — 8 fits no bin
        out = packer.pop()
        toks, segs = out["tokens"], out["segment_ids"]
        np.testing.assert_array_equal(
            toks[0], np.concatenate([d5, [0], np.zeros(3, np.int32)])
        )
        np.testing.assert_array_equal(
            segs[0], [1, 1, 1, 1, 1, 1, 0, 0, 0]
        )
        np.testing.assert_array_equal(
            toks[1], np.concatenate([d3, [0], np.zeros(5, np.int32)])
        )
        np.testing.assert_array_equal(
            segs[1], [1, 1, 1, 1, 0, 0, 0, 0, 0]
        )
        assert packer.pop() is None
        assert packer.density() == pytest.approx(10 / 18)

    def test_long_doc_splits_into_own_segments(self):
        packer = TokenPacker(2, 8, packing="first_fit")  # cap 9
        packer.feed_docs([np.arange(1, 21, dtype=np.int32)])  # +eos = 21
        # chunks 9, 9, 3: third chunk fits neither full bin -> close
        out = packer.pop()
        toks, segs = out["tokens"], out["segment_ids"]
        np.testing.assert_array_equal(segs[0], np.ones(9, np.int32))
        np.testing.assert_array_equal(segs[1], np.ones(9, np.int32))
        np.testing.assert_array_equal(toks[0], np.arange(1, 10))
        np.testing.assert_array_equal(toks[1], np.arange(10, 19))

    def test_state_resume_byte_identical_mid_carry(self):
        rng = np.random.default_rng(5)
        docs = [rng.integers(1, 64, size=int(n)).astype(np.int32)
                for n in rng.integers(2, 12, size=80)]
        a = TokenPacker(2, 8, packing="best_fit")
        a.feed_docs(docs[:40])
        drained = []
        while (got := a.pop()) is not None:
            drained.append(got)
        carry = json.loads(json.dumps(a.state()))  # the wire round trip
        b = TokenPacker(2, 8, packing="best_fit")
        b.restore(carry)
        a.feed_docs(docs[40:])
        b.feed_docs(docs[40:])
        assert a.density() == b.density()
        while True:
            ga, gb = a.pop(), b.pop()
            assert (ga is None) == (gb is None)
            if ga is None:
                break
            np.testing.assert_array_equal(ga["tokens"], gb["tokens"])
            np.testing.assert_array_equal(
                ga["segment_ids"], gb["segment_ids"]
            )

    def test_pending_batches_survive_restore(self):
        a = TokenPacker(2, 8, packing="best_fit")
        rng = np.random.default_rng(9)
        a.feed_docs(rng.integers(1, 64, size=int(n)).astype(np.int32)
                    for n in rng.integers(2, 9, size=30))
        carry = json.loads(json.dumps(a.state()))
        b = TokenPacker(2, 8, packing="best_fit")
        b.restore(carry)
        while (ga := a.pop()) is not None:
            gb = b.pop()
            np.testing.assert_array_equal(ga["tokens"], gb["tokens"])
            np.testing.assert_array_equal(
                ga["segment_ids"], gb["segment_ids"]
            )
        assert b.pop() is None

    def test_best_fit_density_beats_greedy_on_ragged_corpus(self):
        rng = np.random.default_rng(15)
        sizes = rng.choice([2, 6, 10, 15, 16, 21, 25, 31], size=300)
        docs = [np.ones(int(s), np.int32) for s in sizes]
        dens = {}
        for mode in ("first_fit", "best_fit"):
            p = TokenPacker(4, 32, packing=mode)
            p.feed_docs(docs)
            while p.pop() is not None:
                pass
            dens[mode] = p.density()
        assert dens["best_fit"] > dens["first_fit"], dens
