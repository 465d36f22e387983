"""Ask the chip's compiler, without the chip (on-chip-measurement guide §2.3).

The TPU compiler is installed here and compiles for a DESCRIBED ``v5e:2x2``
topology: what it refuses (unaligned tiles, too much fast memory, a program
that does not fit 16 GB, a kernel that cannot be partitioned) it refuses at
no chip time. These are the programs ``chip_smoke.py`` runs, at their real
widths. A compile that passes is not a chip run and is never reported as one.

Rules of this file (the guide's): the topology is described INSIDE a
module-scoped fixture that skips when it cannot be — never at import, in a
``skipif``, a ``parametrize`` argument or conftest.py, and never in a child
process (one process holds libtpu); everything lives in this ONE file so
xdist's ``--dist loadfile`` hands it to one worker; the persistent
compilation cache is off around it (a described-device executable is written
to the cache but cannot be read back without a chip).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke
from tpu_tfrecord.models import (
    DLRMConfig, forward as dlrm_forward, init_params as dlrm_init, lm, sparse_opt_init,
    sparse_train_step,
)
from tpu_tfrecord.models import pipeline as pp
from tpu_tfrecord.models.attention import ring_attention
from tpu_tfrecord.models.interaction import dot_interaction, dot_interaction_pallas

B = chip_smoke.FULL["batch"]            # 16,384
HBM_BYTES = 16 * 10**9                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shaped(tree, sharding):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs), placed by
    ``sharding`` (one for all leaves, or a matching pytree)."""
    if not isinstance(sharding, (dict, list, tuple)):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sharding,
    )


def _entry_lines(hlo: str) -> list:
    """The instructions of the compiled program's ENTRY computation (fused
    computations repeat their roots' names, so a count reads these alone)."""
    entry = hlo[hlo.index("\nENTRY "):]
    return entry[:entry.index("\n}")].splitlines()


def _inside(hlo: str, computation: str) -> list:
    """The instructions of ``computation`` and of every computation it calls
    (a fusion's, a nested loop's body and condition), as lines."""
    bodies = dict(re.findall(r"\n%?([\w.\-]+) \([^\n]*\) -> [^\n]* \{\n(.*?)\n\}", hlo, re.S))
    lines, todo = [], [computation]
    while todo:
        found = bodies[todo.pop()].splitlines()
        lines += found
        todo += [name for line in found for name in re.findall(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", line)]
    return lines


class TestOneChip:
    def test_device_kind_is_one_the_smoke_knows(self, topo):
        assert topo.devices[0].platform == "tpu"
        assert topo.devices[0].device_kind in chip_smoke.KNOWN_DEVICE_KINDS

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_dot_interaction_pallas_forward(self, one_chip, dtype):
        # float32 takes the multi-pass selection matmuls (precision=HIGHEST)
        x = jax.ShapeDtypeStruct((B, 27, 32), dtype, sharding=one_chip)
        hlo = jax.jit(dot_interaction_pallas).lower(x).compile().as_text()
        assert "tpu_custom_call" in hlo

    def test_dot_interaction_pallas_vjp(self, one_chip):
        x = jax.ShapeDtypeStruct((B, 27, 32), jnp.float32, sharding=one_chip)

        def loss(e):
            return dot_interaction(e, True).sum()

        hlo = jax.jit(jax.value_and_grad(loss)).lower(x).compile().as_text()
        assert "tpu_custom_call" in hlo

    def test_split_and_unpack_bits_at_the_wire_width(self, one_chip):
        from tpu_tfrecord.tpu import packed_width

        width = 1 + chip_smoke.NUM_DENSE + packed_width(
            chip_smoke.NUM_CAT, chip_smoke.CAT_BITS
        )
        gb = {"wire": jax.ShapeDtypeStruct((B, width), jnp.int32, sharding=one_chip)}
        split = jax.jit(
            functools.partial(chip_smoke.split_wire, vocab=chip_smoke.HASH_BUCKETS)
        )
        out = split.lower(gb).compile().output_shardings
        assert set(out) == {"label", "dense", "cat"}

    def test_forward_holds_no_copy_of_the_table(self, one_chip):
        """MLPerf widths at 2^15 rows a table (436 MB of float32): scoring
        reads the rows a batch names, so its temp is the size of the batch's
        activations. Before PR 25 a bfloat16 copy of the table (half its
        bytes; 3.49 GB of temp at the benchmark's 2^19 rows) was made per call."""
        cfg = DLRMConfig(num_dense=13, num_categorical=26, vocab_size=1 << 15,
                         embed_dim=128, bottom_mlp=(512, 256, 128),
                         top_mlp=(1024, 1024, 512, 256, 1), interaction="dot")
        params = jax.eval_shape(lambda: dlrm_init(jax.random.key(0), cfg))
        batch = {
            "dense": jax.ShapeDtypeStruct((2048, 13), jnp.float32),
            "cat": jax.ShapeDtypeStruct((2048, 26), jnp.int32),
        }
        compiled = jax.jit(functools.partial(dlrm_forward, cfg=cfg)).lower(
            _shaped(params, one_chip), _shaped(batch, one_chip)).compile()
        table = params["embeddings"]
        table_bytes = table.size * table.dtype.itemsize
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > table_bytes         # the table is in
        assert mem.temp_size_in_bytes < table_bytes // 8

    @pytest.mark.parametrize("config", ["chip_smoke", "mlperf_widths"])
    def test_sparse_train_step_at_the_bench_config_fits_hbm(self, one_chip, config):
        """The two long compiles (~30-60 s each), B=16,384: chip_smoke's
        26 x 2^20 x 32 f32 tables, whose narrow rows keep the update in one
        block (the compiler holds a relayout of such a table as a
        temporary), and the benchmark's 26 x 2^19 x 128, where the loop over
        the blocks of slots carries the donated table in place: the compiled
        step holds no second one."""
        if config == "chip_smoke":
            cfg = chip_smoke.criteo_dlrm_config(chip_smoke.FULL["vocab"])
        else:
            cfg = DLRMConfig(num_dense=13, num_categorical=26, vocab_size=1 << 19,
                             embed_dim=128, bottom_mlp=(512, 256, 128),
                             top_mlp=(1024, 1024, 512, 256, 1), interaction="dot")
        tx = optax.sgd(1e-3)
        params = jax.eval_shape(lambda: dlrm_init(jax.random.key(0), cfg))
        opt = jax.eval_shape(lambda p: sparse_opt_init(p, cfg, tx), params)
        batch = {
            "label": jax.ShapeDtypeStruct((B,), jnp.float32),
            "dense": jax.ShapeDtypeStruct((B, chip_smoke.NUM_DENSE), jnp.float32),
            "cat": jax.ShapeDtypeStruct((B, chip_smoke.NUM_CAT), jnp.int32),
        }
        step = jax.jit(
            functools.partial(sparse_train_step, cfg=cfg, tx=tx),
            donate_argnums=(0, 1),
        )
        compiled = step.lower(
            _shaped(params, one_chip), _shaped(opt, one_chip),
            _shaped(batch, one_chip),
        ).compile()
        mem = compiled.memory_analysis()
        table = params["embeddings"]
        table_bytes = table.size * table.dtype.itemsize
        assert mem.argument_size_in_bytes > table_bytes             # tables are in
        assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
        assert mem.alias_size_in_bytes >= table_bytes               # and donated
        if config == "mlperf_widths":
            assert mem.temp_size_in_bytes < table_bytes // 2

    def test_the_delta_rule_kernel_at_the_cells_shape(self, one_chip):
        """``solar_open2_ep8.score``'s delta-rule layer, [2, 64, 8192, 128] in
        chunks of 64 and grid steps of two heads' 256 tokens, handed what the
        layer has: the three PROJECTIONS bfloat16 as they were written, their
        taps [4, 64 * 128], the decay a channel float32, beta, the segment ids.
        The kernel prepares its own q, k and v (taps, SiLU, unit norm, a grid
        step ahead of the recurrence) and fits VMEM (the compiler refuses one
        that does not); the program's arguments are those operands as they
        are, and nothing of q's size exists beside them and the output."""
        from tpu_tfrecord.models import linear_attn

        shape = (2, 64, 8192, 128)
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        taps = jax.ShapeDtypeStruct((4, 64 * 128), jnp.bfloat16, sharding=one_chip)
        decay = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
        beta = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
        compiled = jax.jit(lambda q, k, v, t, g, b, s: linear_attn._delta_rule_fused(
            q, k, v, g, b, s, 128 ** -0.5, linear_attn._TILES[0], taps=t)).lower(
                x, x, x, (taps,) * 3, decay, beta, segs).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes == 4 * np.prod(shape)
        assert mem.argument_size_in_bytes == ((3 * 2 + 4) * np.prod(shape) + 4 * np.prod(shape[:3]) + 4 * 2 * 8192
                                              + 3 * 2 * 4 * 64 * 128)
        assert mem.temp_size_in_bytes < 4 * np.prod(shape) // 8

    def test_the_delta_rule_kernel_under_one_decay_a_token_at_the_cells_shape(self, one_chip):
        """``gigachat35_ep16.score``'s delta-net layer: v's projection [2, 64,
        8192, 128] over q's and k's at 32 heads, all three bfloat16 as they were
        written, their taps, a decay and a beta [2, 64, 8192]. The chip's
        compiler takes the kernel's other form (lane rolls of a row, bfloat16
        blocks, a key head's block index for its projections AND its taps, q
        and k prepared once a key head), and the program's arguments are what
        the mechanism has: no copy of q or k to 64 heads, no decay of v's
        shape, nothing float32 of v's size but the output. With the prepared
        q, k and v handed back (the probed layer) they are three more outputs
        in bfloat16, at their own heads."""
        from tpu_tfrecord.models import linear_attn

        b, h, hk, l, d = 2, 64, 32, 8192, 128
        keys = jax.ShapeDtypeStruct((b, hk, l, d), jnp.bfloat16, sharding=one_chip)
        values = jax.ShapeDtypeStruct((b, h, l, d), jnp.bfloat16, sharding=one_chip)
        taps = tuple(jax.ShapeDtypeStruct((4, n * d), jnp.bfloat16, sharding=one_chip) for n in (hk, hk, h))
        by_token = jax.ShapeDtypeStruct((b, h, l), jnp.float32, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((b, l), jnp.int32, sharding=one_chip)
        operands = (2 * (2 * b * hk * l * d + b * h * l * d) + 2 * 4 * b * h * l + 4 * b * l
                    + 2 * 4 * d * (hk + hk + h))                # 545 MB where the broadcasts are 2.1 GB
        for handed, more in ((False, 0), (True, 2 * (2 * b * hk * l * d + b * h * l * d))):
            compiled = jax.jit(lambda q, k, v, t, g, bt, s: linear_attn._delta_rule_fused(
                q, k, v, g, bt, s, d ** -0.5, linear_attn._TILES[0], taps=t, handed=handed)).lower(
                    keys, keys, values, taps, by_token, by_token, segs).compile()
            assert "tpu_custom_call" in compiled.as_text()
            mem = compiled.memory_analysis()
            assert 0 <= mem.output_size_in_bytes - (4 * b * h * l * d + more) < 1024      # a tuple's table
            assert mem.argument_size_in_bytes == operands
            assert mem.temp_size_in_bytes < 4 * b * h * l * d // 8

    def test_the_delta_rule_kernel_at_keys_of_96_under_values_of_192(self, one_chip):
        """``olmo_hybrid_7b_pp4.score``'s delta-net layer: q's and k's projections
        [2, 30, 8192, 96] and v's [2, 30, 8192, 192], bfloat16 as they were
        written, their float32 taps, a decay and a beta [2, 30, 8192]. The chip's
        compiler takes the kernel at widths that fill no whole lane block (a tile
        of q and k padded to 128 lanes and of v to 256 as it is read into VMEM, a
        state of [128, 256] in scratch), and memory holds the PUBLISHED widths
        alone: the arguments are the operands as they are, the output is
        [.., 192] float32, with the prepared q, k and v handed back three more
        outputs at 96, 96 and 192, and nothing padded is written between."""
        from tpu_tfrecord.models import linear_attn

        b, h, l, dk, dv = 2, 30, 8192, 96, 192
        keys = jax.ShapeDtypeStruct((b, h, l, dk), jnp.bfloat16, sharding=one_chip)
        values = jax.ShapeDtypeStruct((b, h, l, dv), jnp.bfloat16, sharding=one_chip)
        taps = tuple(jax.ShapeDtypeStruct((4, h * w), jnp.float32, sharding=one_chip) for w in (dk, dk, dv))
        by_token = jax.ShapeDtypeStruct((b, h, l), jnp.float32, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((b, l), jnp.int32, sharding=one_chip)
        operands = 2 * b * h * l * (2 * dk + dv) + 2 * 4 * b * h * l + 4 * b * l + 4 * 4 * h * (2 * dk + dv)
        for handed, more in ((False, 0), (True, 2 * b * h * l * (2 * dk + dv))):
            compiled = jax.jit(lambda q, k, v, t, g, bt, s: linear_attn._delta_rule_fused(
                q, k, v, g, bt, s, dk ** -0.5, linear_attn._TILES[0], taps=t, handed=handed)).lower(
                    keys, keys, values, taps, by_token, by_token, segs).compile()
            hlo = compiled.as_text()
            assert "tpu_custom_call" in hlo and f"f32[{b},{h},{l},{dv}]" in hlo
            assert not re.search(rf"\[{b},{h},{l},(128|256)\]", hlo)       # no padded copy of q, k, v or o
            mem = compiled.memory_analysis()
            assert 0 <= mem.output_size_in_bytes - (4 * b * h * l * dv + more) < 1024      # a tuple's table
            assert 0 <= mem.argument_size_in_bytes - operands < 4096      # the taps' 2,880 columns in tiles of 128

    def test_the_solar_patterns_score_holds_no_loop_under_the_scan(self, one_chip, monkeypatch):
        """``lm.score`` for the softmax / delta-rule period at the cell's row
        shape and delta-rule widths (the rest narrow: this is about one
        scope), steered onto the TPU's paths as a described chip cannot steer
        it: under ``tfr.kda_scan`` the compiled program holds the kernel
        once a layer and no ``while`` (the plain form's loops over head
        groups and chunks), and of float32 arrays of q's size only the
        kernel's output (the projections come in bfloat16 from ``tfr.kda_proj``,
        as the log-decay does, and the kernel prepares its own q, k and v: since
        PR 48 ``tfr.kda_conv`` holds no operation)."""
        from tpu_tfrecord.models import linear_attn

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = lm.PatternLMConfig(
            vocab_size=2048, d_model=256, layer_pattern=("gqa", "kda", "kda", "kda"),
            n_heads=2, n_kv_heads=1, head_dim=128, kda_heads=64, kda_head_dim=128, conv_taps=4,
            gate_rank=32, n_experts=8, experts_held=8, top_k=2, d_expert=128, n_shared=1,
            max_len=8192, dtype=jnp.bfloat16, attn_block=1024, kda_chunk=64, expert_tile=256,
            head_block=2048)
        q_shape = (2, cfg.kda_heads, cfg.max_len, cfg.kda_head_dim)
        assert linear_attn.fused_tile(q_shape, cfg.kda_chunk) == linear_attn._TILES[0]
        params = jax.tree.map(lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip),
                              lm.pattern_param_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        rows = jax.ShapeDtypeStruct((2, cfg.max_len + 1), jnp.int32, sharding=one_chip)
        at = jax.ShapeDtypeStruct((2, 4), jnp.int32, sharding=one_chip)
        head = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        hlo = jax.jit(lambda p, t, s, a, h: lm.score(p, t, s, a, cfg, h)).lower(
            params, rows, rows, at, head).compile().as_text()
        scan = [line for line in _entry_lines(hlo) if re.search(r'op_name="[^"]*tfr\.kda_scan', line)]
        assert sum("custom_call_target=\"tpu_custom_call\"" in line for line in scan) == 3
        assert not [line for line in scan if re.search(r"= \S+ while\(", line)]
        q_sized = "f32[" + ",".join(map(str, q_shape)) + "]"
        assert sum(bool(re.match(rf"\s*(ROOT )?%?\S+ = {re.escape(q_sized)}", line)) for line in scan) == 3
        assert "tfr.kda_conv" not in hlo

    @pytest.mark.parametrize("kind, widths", [
        ("kda", dict(d_model=4096, gate_rank=128)),
        ("gdn", dict(d_model=7168, gdn_key_heads=32, centred_norms=True))],
        ids=["solar_open2_ep8", "gigachat35_ep16"])
    def test_a_delta_rule_layer_hands_its_kernel_the_projections(self, one_chip, monkeypatch, kind, widths):
        """One delta-rule layer of each cell alone at the cell's shape, compiled for
        the chip as a TPU runs it (until PR 48 the convolution wrote five bfloat16
        arrays a layer under the conv scope and the kernel read three of them):
        under the conv scope NO operation is left, so no array of q's or of v's
        size is written there in either dtype; under the scan scope ONE custom
        call, and of arrays of those sizes its float32 output and, because this
        layer is probed, exactly three bfloat16 ones: the q, k and v the kernel
        prepared and consumed, written beside the output for the probe to read."""
        from tpu_tfrecord.models import linear_attn

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = lm.PatternLMConfig(
            vocab_size=256, layer_pattern=(kind,), ffn_pattern=("dense",), kda_heads=64, kda_head_dim=128,
            conv_taps=4, max_len=8192, kda_chunk=64, dtype=jnp.bfloat16, **widths)
        assert linear_attn.fused_tile((2, 64, 8192, 128), cfg.kda_chunk) == linear_attn._TILES[0]
        layer = lm.pattern_param_shapes(cfg)["layers"][0]
        p = {name: jax.ShapeDtypeStruct(*sd, sharding=one_chip) for name, sd in layer.items()
             if name not in ("dense", "ffn_norm")}
        x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
        head = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        mixer = {"kda": lm.kda_mixer, "gdn": lm.gdn_mixer}[kind]
        entry = _entry_lines(
            jax.jit(lambda p, x, s, h: mixer(p, x, s, cfg, h)).lower(p, x, segs, head).compile().as_text())

        def written(scope, dtype, heads):  # arrays [2, heads, 8192, 128] of dtype that operations under scope write
            sized, count = f"{dtype}[2,{heads},8192,128]", 0
            for line in entry:
                if not re.search(rf'op_name="[^"]*tfr\.{kind}_{scope}', line):
                    continue
                result, op = re.match(r"\s*(?:ROOT )?\S+ = (.*?) ([a-z][\w\-]*)\(", re.sub(r"\{[^{}]*\}", "", line)).groups()
                if op not in ("get-tuple-element", "tuple", "bitcast"):     # those name an array again
                    count += result.count(sized)
            return count

        key_heads = cfg.gdn_key_heads or 64        # q's and k's heads, fewer than v's 64 or as many
        assert not any(re.search(rf'op_name="[^"]*tfr\.{kind}_conv', line) for line in entry)
        assert written("scan", "f32", 64) == 1 and not (key_heads != 64 and written("scan", "f32", key_heads))
        if key_heads == 64:
            assert written("scan", "bf16", 64) == 3
        else:
            assert written("scan", "bf16", key_heads) == 2 and written("scan", "bf16", 64) == 1
        assert sum("custom_call_target=\"tpu_custom_call\"" in line for line in entry) == 1

    def test_a_delta_net_layer_of_96_under_192_holds_one_float32_array_of_its_values_size(self, one_chip, monkeypatch):
        """``olmo_hybrid_7b_pp4.score``'s delta-net layer alone at the cell's shape,
        compiled for the chip as a TPU runs it: ONE custom call, no operation under
        the conv scope, the projections written once in bfloat16 at their published
        widths where the kernel reads them, and of float32 arrays of v's size the
        kernel's output ALONE: the gate's projection stays bfloat16 until the gate's
        own fusion reads it (left to itself the compiler widens it inside the
        projection and copies 0.38 GB of float32 into the output's layout)."""
        from tpu_tfrecord.models import linear_attn

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = lm.PatternLMConfig(
            vocab_size=256, d_model=3840, layer_pattern=("gdn",), ffn_pattern=("dense",), kda_heads=30,
            kda_head_dim=96, gdn_value_dim=192, gdn_neg_eigval=True, gdn_gate="silu", branch_norms=True,
            pre_norms=False, conv_taps=4, max_len=8192, kda_chunk=64, dtype=jnp.bfloat16)
        assert linear_attn.fused_tile((2, 30, 8192, 192), cfg.kda_chunk, 96) == linear_attn._TILES[0]
        layer = lm.pattern_param_shapes(cfg)["layers"][0]
        assert "attn_norm" not in layer and "ffn_norm" not in layer
        p = {name: jax.ShapeDtypeStruct(*sd, sharding=one_chip) for name, sd in layer.items()
             if name not in ("dense", "post_ffn_norm")}
        x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
        entry = _entry_lines(jax.jit(lambda p, x, s: lm.gdn_mixer(p, x, s, cfg)[0]).lower(p, x, segs).compile().as_text())
        assert sum("custom_call_target=\"tpu_custom_call\"" in line for line in entry) == 1
        assert not any(re.search(r'op_name="[^"]*tfr\.gdn_conv', line) for line in entry)
        results = [re.match(r"\s*(?:ROOT )?\S+ = (.*?) ([a-z][\w\-]*)\(", re.sub(r"\{[^{}]*\}", "", line)) for line in entry]
        written = [m.group(1) for m in results if m and m.group(2) not in ("get-tuple-element", "tuple", "bitcast", "parameter")]
        values = re.compile(r"\[(2,30,8192,192|30,192,2,8192|2,8192,30,192|2,8192,5760)\]")
        assert sum(bool(values.search(r)) for r in written if r.startswith("f32")) == 1, written
        assert sum(r.count("bf16[2,30,8192,192]") for r in written) == 2           # v's projection and the gate's
        assert sum(r.count("bf16[2,30,8192,96]") for r in written) == 2            # q's and k's

    def test_the_state_space_kernel_at_the_cells_shape(self, one_chip):
        """``nemotron_twotower_ep2.score``'s state-space layer: the convolution's
        output [2, 8192, 6144] bfloat16 as ONE operand (64 heads of 64, then B
        and C at 8 groups of 128), a step and a decay [2, 8192, 64] float32. The
        chip's compiler takes the kernel (a transpose of 16 rows' numbers a
        chunk, bfloat16 products in three parts, a group's B and C by block
        index), and the program's arguments are what the mechanism has: no
        copy of B or C to 64 heads (0.54 GB where they are 0.07), nothing
        float32 of x's size but the output."""
        from tpu_tfrecord.models import linear_attn

        b, l, h, g, p, n = 2, 8192, 64, 8, 64, 128
        xbc = jax.ShapeDtypeStruct((b, l, h * p + 2 * g * n), jnp.bfloat16, sharding=one_chip)
        by_token = jax.ShapeDtypeStruct((b, l, h), jnp.float32, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((b, l), jnp.int32, sharding=one_chip)
        compiled = jax.jit(lambda x, dt, a, s: linear_attn._ssm_fused(
            x, dt, a, s, heads=h, groups=g, state=n, tile=linear_attn._TILES[0])).lower(
                xbc, by_token, by_token, segs).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes == 4 * b * l * h * p
        assert mem.argument_size_in_bytes == 2 * b * l * (h * p + 2 * g * n) + 2 * 4 * b * l * h + 4 * b * l
        # the segment ids a lane block wide and the two running arrays, tokens along the lanes
        assert mem.temp_size_in_bytes < 4 * b * l * h * p // 8

    def test_a_state_space_layer_hands_its_kernel_what_the_convolution_wrote(self, one_chip, monkeypatch):
        """One state-space layer of the cell alone at the cell's shape, compiled
        for the chip as a TPU runs it: the kernel once, no array of B or C at 64
        heads in any dtype, no float32 array the size of the convolution's
        output, and of float32 arrays of x's size under the scan scope only the
        kernel's output."""
        from tpu_tfrecord.models import linear_attn

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = lm.PatternLMConfig(
            vocab_size=256, d_model=2688, layer_pattern=("ssm",), ffn_pattern=("none",), kda_heads=64,
            kda_head_dim=64, ssm_state=128, ssm_groups=8, conv_taps=4, max_len=8192, kda_chunk=128,
            dtype=jnp.bfloat16)
        assert linear_attn.ssm_tile((2, 8192, 6144), cfg.dtype, 64, 8, 128, 128) == linear_attn._TILES[0]
        layer = lm.pattern_param_shapes(cfg)["layers"][0]
        assert layer["w_in"][0] == (2688, 10304) and "router" not in layer
        p = {name: jax.ShapeDtypeStruct(*sd, sharding=one_chip) for name, sd in layer.items()}
        x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
        head = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        entry = _entry_lines(
            jax.jit(lambda p, x, s, h: lm.ssm_mixer(p, x, s, cfg, h)).lower(p, x, segs, head).compile().as_text())
        results = [re.match(r"\s*(?:ROOT )?\S+ = (.*?) ([a-z][\w\-]*)\(", re.sub(r"\{[^{}]*\}", "", line))
                   for line in entry]
        written = [(m.group(1), line) for m, line in zip(results, entry)
                   if m and m.group(2) not in ("get-tuple-element", "tuple", "bitcast", "parameter")]
        assert sum("custom_call_target=\"tpu_custom_call\"" in line for line in entry) == 1
        for sized in ("[2,8192,64,128]", "[2,64,8192,128]", "[2,8192,8192]", "f32[2,8192,6144]", "f32[2,1,8192,6144]"):
            assert not [r for r, _ in written if sized in r], sized
        scan = [r for r, line in written if re.search(r'op_name="[^"]*tfr\.ssm_scan', line)]
        assert sum(r.count("f32[2,8192,4096]") for r in scan) == 1
        assert sum(r.count("bf16[2,8192,6144]") + r.count("bf16[2,1,8192,6144]") for r, _ in written) <= 2

    def test_the_selection_kernel_at_the_cells_shape(self, one_chip):
        """``deepseek_v32_exp_ep16.score``'s selection, 64 index heads of 128
        over one row of 16,384 tokens, 2,048 keys a query: the kernel fits
        VMEM with a block's scores as its scratch, and nothing but the mask
        (one byte a pair) and the counts leaves it: no float32 score reaches
        the chip's main memory."""
        from tpu_tfrecord.models import sparse_attn

        l = 16384
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in (
            ((1, 64, l, 128), jnp.bfloat16), ((1, l, 128), jnp.bfloat16),
            ((1, l, 64), jnp.float32), ((1, l), jnp.int32))]
        compiled = jax.jit(lambda q, k, w, s: sparse_attn._select_fused(
            q, k, w, s, 2048, (sparse_attn._BLOCK_Q, sparse_attn._BLOCK_K))).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert l * l + l * 4 <= mem.output_size_in_bytes < l * l + l * 4 + 4096   # the mask, the counts
        assert mem.temp_size_in_bytes < l * l // 8

    @pytest.mark.parametrize("rows, heads, l, selection", [(1, 128, 16384, True), (2, 16, 8192, False)],
                             ids=["under_a_selection", "packed_rows"])
    def test_the_attention_kernel_at_the_cells_shape(self, one_chip, rows, heads, l, selection):
        """Latent attention's queries and keys in their two parts, 128 plain and 64
        rotary columns (the rotary keys one head for all), against 128-wide
        values: ``deepseek_v32_exp_ep16.score``'s one row of 16,384 tokens and 128
        heads with the mask of kept keys as a further input, one [1024, 1024]
        block of it a pair of blocks, and ``kimi_vl_a3b_lm.score``'s two packed
        rows of 8,192 without one. Each kind of pair is a body of its own in the
        one kernel, the 64-wide product beside the 128-wide one in each: it fits
        VMEM, and a layer's call is one custom call."""
        from tpu_tfrecord.models.attention import flash_attention_widths

        q, q_rope, k_rope = (jax.ShapeDtypeStruct((rows, h, l, d), jnp.bfloat16, sharding=one_chip)
                             for h, d in ((heads, 128), (heads, 64), (1, 64)))
        segs = jax.ShapeDtypeStruct((rows, l), jnp.int32, sharding=one_chip)
        keep = [jax.ShapeDtypeStruct((rows, l, l), jnp.int8, sharding=one_chip)] * selection
        compiled = jax.jit(lambda q, k, v, s, q_rope, k_rope, *m: flash_attention_widths(
            q, k, v, s, 0.135, 1024, 1024, *m, q_rope=q_rope, k_rope=k_rope)).lower(
                q, q, q, segs, q_rope, k_rope, *keep).compile()
        assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") == 1
        assert compiled.memory_analysis().output_size_in_bytes == 2 * rows * heads * l * 128

    @pytest.mark.parametrize("rows, l, widths", [
        (1, 16384, dict(d_model=7168, n_heads=128, q_rank=1536, rope_theta=10000.0,
                        rope_scaling=(40.0, 4096.0, 32.0, 1.0))),
        (2, 8192, dict(d_model=2048, n_heads=16, rope_theta=800000.0))],
        ids=["deepseek_v32_exp_ep16", "kimi_vl_a3b_lm"])
    def test_a_latent_attention_layer_joins_nothing_in_memory(self, one_chip, monkeypatch, rows, l, widths):
        """The mixer alone at the two cells' shapes (compressed queries and YaRN, and
        neither), compiled for the chip as a TPU runs it: each of the five arrays
        the attention reads is written by its projection and handed to the kernel
        as it is. No four-axis array as wide as both parts of q and k (192, laid
        out 256 wide) or as the keys beside the values (256) is in the program, no
        rotary key copied to every head, and the kernel is there."""
        monkeypatch.setattr(lm.jax, "default_backend", lambda: "tpu")     # the described chip's branch
        cfg = lm.PatternLMConfig(
            vocab_size=256, layer_pattern=("mla",), ffn_pattern=("dense",), qk_nope_dim=128,
            qk_rope_dim=64, v_head_dim=128, kv_rank=512, max_len=l, attn_block=1024,
            dtype=jnp.bfloat16, **widths)
        layer = lm.pattern_param_shapes(cfg)["layers"][0]
        p = {name: jax.ShapeDtypeStruct(*layer[name], sharding=one_chip)
             for name in ("attn_norm", "wq_a", "q_norm", "wq_b", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
             if name in layer}
        x = jax.ShapeDtypeStruct((rows, l, cfg.d_model), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((rows, l), jnp.int32, sharding=one_chip)
        text = jax.jit(lambda p, x, s: lm.mla_mixer(p, x, s, cfg)).lower(p, x, segs).compile().as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        heads = rf"\[{rows},{cfg.n_heads},{l},"
        assert not re.findall(heads + r"(192|256)\]", text)
        assert re.findall(heads + r"128\]", text) and re.findall(heads + r"64\]", text)
        assert re.findall(rf"\[{rows},1,{l},64\]", text)                 # the rotary keys: one head

    @pytest.mark.parametrize("rows, l, pairs, widths", [
        (2, 8192, 36, dict(d_model=4096, n_heads=64)),
        (1, 32768, 528, dict(d_model=3072, n_heads=48, qk_norm=True))],
        ids=["solar_open2_ep8", "trinity_large_ep8"])
    def test_a_full_softmax_layer_reads_its_key_heads_as_they_lie(self, one_chip, monkeypatch, rows, l,
                                                                 pairs, widths):
        """The two cells' full layers (``gqa_mixer`` alone), compiled for the chip as a
        TPU runs them since PR 43: the repo's kernel without a window under the
        kernel's own limit of fast memory (the compiler refuses a kernel that
        passes ``_VMEM_LIMIT``), its grid the row's block pairs at or under the
        diagonal, and K and V handed to it as the 8 heads the projections wrote:
        of the query heads' shape the program holds q and the kernel's output and
        nothing else: no K or V copied 8 or 6 times, a quarter and more of the
        layer's temporaries when JAX's kernel was handed them."""
        from tpu_tfrecord.models.attention import _grid_pairs

        monkeypatch.setattr(lm.jax, "default_backend", lambda: "tpu")     # the described chip's branch
        cfg = lm.PatternLMConfig(
            vocab_size=256, layer_pattern=("gqa",), ffn_pattern=("dense",), n_kv_heads=8, head_dim=128,
            max_len=l, attn_block=1024, dtype=jnp.bfloat16, **widths)
        layer = lm.pattern_param_shapes(cfg)["layers"][0]
        p = {name: jax.ShapeDtypeStruct(*sd, sharding=one_chip) for name, sd in layer.items()
             if name not in ("dense", "ffn_norm")}
        x = jax.ShapeDtypeStruct((rows, l, cfg.d_model), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((rows, l), jnp.int32, sharding=one_chip)
        compiled = jax.jit(lambda p, x, s: lm.gqa_mixer(p, x, s, cfg)).lower(p, x, segs).compile()
        text = compiled.as_text()
        entry = _entry_lines(text)
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        (call,) = [line for line in entry if "tpu_custom_call" in line]
        heads, held = f"bf16[{rows},{cfg.n_heads},{l},128]", f"bf16[{rows},8,{l},128]"
        made, read = ([line.split(" = ")[0].strip() for line in entry if f" = {shape}" in line]
                      for shape in (heads, held))
        assert len(made) == 2 and call.split(" = ")[0].strip() in made        # q, and what the kernel wrote
        assert len(read) == 2 and all(f"{name}," in call or f"{name})" in call for name in read)   # k and v
        assert not re.findall(re.escape(heads) + r"[^ ]* (broadcast|copy)\(", text)
        # the grid's third axis: the two tables of block indices, a pair an entry
        assert len(_grid_pairs(l, 1024, 1024)) == pairs and text.count(f"s32[{pairs}]") >= 2
        q_bytes = 2 * rows * cfg.n_heads * l * 128
        assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * q_bytes   # q, the gate, the output's share

    def test_the_windowed_attention_kernel_at_the_cells_shape(self, one_chip):
        """``trinity_large_ep8.score``'s sliding layers: 48 query heads on 8
        key-value heads of 128 over one row of 32,768 tokens under 4,096 keys.
        The grid is the band's 150 block pairs of the row's 528, the trailing
        block's body beside the three kinds there were: it fits VMEM, a layer's
        call is one custom call, and K and V are read as the 8 heads they are."""
        from tpu_tfrecord.models.attention import _grid_pairs, flash_attention_widths

        l = 32768
        q = jax.ShapeDtypeStruct((1, 48, l, 128), jnp.bfloat16, sharding=one_chip)
        kv = jax.ShapeDtypeStruct((1, 8, l, 128), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((1, l), jnp.int32, sharding=one_chip)
        compiled = jax.jit(lambda q, k, v, s: flash_attention_widths(
            q, k, v, s, 128 ** -0.5, 1024, 1024, window=4096)).lower(q, kv, kv, segs).compile()
        assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") == 1
        assert compiled.memory_analysis().output_size_in_bytes == 2 * 48 * l * 128
        assert (len(_grid_pairs(l, 1024, 1024, 4096)), len(_grid_pairs(l, 1024, 1024))) == (150, 528)

    def test_a_block_diffusion_layer_at_the_cells_shape_holds_no_mask_of_the_rows_square(self, one_chip,
                                                                                        monkeypatch):
        """``sdar_30b_a3b_pp8.score``'s layers (``gqa_mixer_probed(streams=True)`` alone): 32
        query heads on 4 key-value heads of 128 over one row of TWO streams of 8,192
        tokens under the block mask of 4. One custom call, under the kernel's own
        limit of fast memory; its grid the 80 pairs the mask can see (the clean
        stream's 36, the noised queries' 36 clean key blocks and 8 own blocks) where a
        causal row of 16,384 walks 136; K and V handed to it as the 4 heads the
        projections wrote; and no operand of the row's square anywhere: two integers a
        token decide the mask."""
        from tpu_tfrecord.models.attention import _grid_pairs

        monkeypatch.setattr(lm.jax, "default_backend", lambda: "tpu")     # the described chip's branch
        l = 8192
        cfg = lm.PatternLMConfig(
            vocab_size=256, d_model=2048, layer_pattern=("bda",), ffn_pattern=("dense",), n_heads=32,
            n_kv_heads=4, head_dim=128, qk_norm=True, gqa_gate=False, diffusion_block=4, mask_id=255,
            rope_theta=1e6, max_len=l, attn_block=1024, dtype=jnp.bfloat16)
        layer = lm.pattern_param_shapes(cfg)["layers"][0]
        p = {name: jax.ShapeDtypeStruct(*sd, sharding=one_chip) for name, sd in layer.items()
             if name not in ("dense", "ffn_norm")}
        assert "wg" not in p and p["q_norm"].shape == (128,)
        x = jax.ShapeDtypeStruct((1, 2 * l, 2048), jnp.bfloat16, sharding=one_chip)
        segs = jax.ShapeDtypeStruct((1, 2 * l), jnp.int32, sharding=one_chip)
        compiled = jax.jit(lambda p, x, s: lm.gqa_mixer_probed(p, x, s, cfg, streams=True)[0]).lower(
            p, x, segs).compile()
        text = compiled.as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
        (call,) = [line for line in _entry_lines(text) if "tpu_custom_call" in line]
        assert f"bf16[1,32,{2 * l},128]" in call and call.count(f"bf16[1,4,{2 * l},128]") >= 2
        pairs = len(_grid_pairs(2 * l, 1024, 1024, streams=True))
        assert (pairs, len(_grid_pairs(2 * l, 1024, 1024))) == (80, 136) and text.count(f"s32[{pairs}]") >= 2
        assert not re.findall(rf"\[(?:\d+,)*(?:{l},{l}|{2 * l},{2 * l})\]", text)
        q_bytes = 2 * 32 * 2 * l * 128
        assert compiled.memory_analysis().temp_size_in_bytes < 3.5 * q_bytes   # q, the output, k, v and the norm's float32

    @pytest.mark.parametrize("t, d, f, n_experts, held, top_k, laid", [
        (16384, 2048, 1408, 64, 64, 6, (384 + 64 * 2) * 256 + 1),      # kimi_vl_a3b_lm.score: read back
        (16384, 7168, 2048, 256, 16, 8, None),                          # gigachat35_ep16.score: added as computed
    ], ids=["read_back", "added_as_computed"])
    def test_the_expert_loops_at_tiles_of_1024(self, one_chip, t, d, f, n_experts, held, top_k, laid):
        """``moe.held_experts_apply`` at two cells' shapes under ``expert_tile=1024``:
        the program for the chip holds TWO loops under ``tfr.moe_experts`` (whole
        tiles, then tails of 256 rows), the buffer of the read-back form is the
        worst case in units (131,073 rows where whole tiles alone took 163,841)
        with its write inside the last product of either loop, and nothing else
        of T * top_k rows or more of the model's width exists in either form.
        A loop's body works out nothing about where its tile is: no ``while``
        inside it (a search), the tile's visits a ``dynamic-slice`` of the padded
        order, and its ONE gather the tile's rows of x; where the rows are added
        as computed also the gates' gather and the one scatter."""
        from tpu_tfrecord.models import moe

        shapes = {"router": ((d, n_experts), jnp.float32), "w_gate": ((held, d, f), jnp.bfloat16),
                  "w_up": ((held, d, f), jnp.bfloat16), "w_down": ((held, f, d), jnp.bfloat16)}
        p = {k: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for k, (shape, dtype) in shapes.items()}
        x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=one_chip)
        hlo = jax.jit(lambda p, x: moe.held_experts_apply(
            p, x, held_offset=0, top_k=top_k, tile=1024)).lower(p, x).compile().as_text()
        loops = [line for line in _entry_lines(hlo) if re.search(r" while\(", line)]
        assert len(loops) == 2 and all(re.search(r'op_name="[^"]*tfr\.moe_experts/while', line) for line in loops)
        tall = {int(rows) for rows in re.findall(rf"\[(\d+),{d}\]", hlo) if int(rows) >= t * top_k}
        assert tall == ({laid} if laid else set())
        if laid:  # the write of a tile stays in the product that makes it, in both bodies
            assert not re.findall(r"\n\s*%?\S+ = \S+ dynamic-update-slice\(", hlo)
            assert len(re.findall(r"\n\s*ROOT %?\S+ = \S+ dynamic-update-slice\(", hlo)) == 2
        for loop, rows in zip(loops, (1024, 256)):
            body = _inside(hlo, re.search(r"body=%?([\w.\-]+)", loop).group(1))
            ops = [re.match(r"\s*(?:ROOT )?%?\S+ = .*? ([a-z][\w\-]*)\(", re.sub(r"\{[^{}]*\}", "", line)) for line in body]
            ops = [op.group(1) for op in ops if op]
            assert "while" not in ops and "sort" not in ops
            assert (ops.count("gather"), ops.count("scatter")) == ((1, 0) if laid else (2, 1))
            assert [line for line in body if re.search(
                rf"= s32\[{rows}\]\S* dynamic-slice\(%?\S+, %?\S+\), dynamic_slice_sizes=\{{{rows}\}}", line)]
            gathered = [line for line in body if re.search(r" gather\(", line)]
            assert sum(bool(re.search(rf"= bf16\[{rows},{d}\]", line)) for line in gathered) == 1

    @pytest.mark.parametrize("d, v", [(2048, 163840), (3072, 25024), (7168, 16160)],
                             ids=["kimi_vl_a3b_lm", "a_ragged_vocabulary", "ragged_and_the_width_cut_in_two"])
    def test_the_head_keeps_its_logits_on_the_chip(self, one_chip, monkeypatch, d, v):
        """``lm.score`` at a cell's head shape (2 rows of 8,192, the rest narrow:
        this is about one scope), steered onto the TPU's paths: under
        ``tfr.lm_head`` the compiled program holds exactly one kernel, of float32
        arrays with the vocabulary's columns only the sampled positions'
        ``[2, 4, V]``, and no copy of the head: the kernel reads the weights where
        they lie, and a vocabulary that is not whole 128s lies COLUMN-major on the
        chip (the compiler's own choice for a parameter whose minor dimension it
        would have to pad), which the kernel takes as ``head.T``, a bitcast; the
        third shape is ragged AND cut in two products with a float32 accumulator.
        The size rule (``_MIN_LOGITS``) is set aside: this is about the kernel
        wherever it will run. That every token cell's head has a tiling,
        tests/test_head.py holds."""
        from tpu_tfrecord.models import head

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(head, "_MIN_LOGITS", 0)      # the kernel at every shape, also those the size rule leaves to the plain form
        assert head.head_tile(2 * 8192, d, v, jnp.bfloat16) is not None
        cfg = lm.PatternLMConfig(
            vocab_size=v, d_model=d, layer_pattern=("gqa",), ffn_pattern=("dense",), n_heads=2, n_kv_heads=1,
            head_dim=128, d_dense=256, max_len=8192, dtype=jnp.bfloat16, attn_block=1024, head_block=2048)
        params = jax.tree.map(lambda sd: jax.ShapeDtypeStruct(*sd, sharding=one_chip),
                              lm.pattern_param_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        rows = jax.ShapeDtypeStruct((2, cfg.max_len + 1), jnp.int32, sharding=one_chip)
        at = jax.ShapeDtypeStruct((2, 4), jnp.int32, sharding=one_chip)
        hlo = jax.jit(lambda p, t, s, a: lm.score(p, t, s, a, cfg)).lower(params, rows, rows, at).compile().as_text()
        scope = [line for line in _entry_lines(hlo) if re.search(r'op_name="[^"]*tfr\.lm_head', line)]
        assert sum("custom_call_target=\"tpu_custom_call\"" in line for line in scope) == 1
        wide = set(re.findall(rf"f32\[[\d,]*\b{v}\]", hlo))
        assert wide == {f"f32[2,4,{v}]"}
        layout = "{0,1" if v % 128 else "{1,0"        # as the chip's compiler lays the parameter
        assert re.search(rf"bf16\[{d},{v}\]{re.escape(layout)}[^}}]*}} parameter\(", hlo)
        assert not re.search(rf"bf16\[({d},{v}|{v},{d})\]\S* (copy|transpose|fusion)\(", hlo)

    def test_lm_train_step_on_dp(self, topo):
        """examples/train_lm.py's widths on a one-device ``data`` mesh."""
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        cfg = lm.LMConfig(
            vocab_size=256, d_model=64, n_heads=4, n_layers=2, max_len=64
        )
        tx = optax.adam(3e-3)
        repl = NamedSharding(mesh, P())
        params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
        opt = jax.eval_shape(tx.init, params)
        toks = jax.ShapeDtypeStruct(
            (32, 65), jnp.int32, sharding=NamedSharding(mesh, P("data", None))
        )
        step = jax.jit(functools.partial(
            lm.train_step, cfg=cfg, tx=tx, mesh=mesh, data_axis="data"))
        step.lower(_shaped(params, repl), _shaped(opt, repl), toks).compile()


class TestFourChips:
    """One program across the four described devices: the collectives the
    compiler put in are the contract (ring = permutes, never a gather)."""

    def test_pipeline_apply_two_stages(self, topo):
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pipe", "data"))

        def stage(p, x):
            return jnp.tanh(x @ p["w"])

        w = jax.ShapeDtypeStruct(
            (2, 128, 128), jnp.float32, sharding=NamedSharding(mesh, P("pipe"))
        )
        xs = jax.ShapeDtypeStruct(
            (8, 16, 128), jnp.float32,
            sharding=pp.microbatch_sharding(mesh, ndim=3, batch_spec=P("data")),
        )
        fn = jax.jit(lambda p, xs: pp.pipeline_apply(
            stage, p, xs, mesh, batch_spec=P("data")))
        hlo = fn.lower({"w": w}, xs).compile().as_text()
        assert "collective-permute" in hlo
        assert "all-gather" not in hlo

    def test_zigzag_ring_attention_seq_two(self, topo):
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "seq"))
        sh = NamedSharding(mesh, P("data", "seq", None, None))
        q = jax.ShapeDtypeStruct((4, 2048, 8, 64), jnp.float32, sharding=sh)
        fn = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, seq_axis="seq", data_axis="data", causal=True,
            zigzag=True))
        hlo = fn.lower(q, q, q).compile().as_text()
        assert "collective-permute" in hlo
        assert "all-gather" not in hlo
