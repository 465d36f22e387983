"""chip_smoke.py, rehearsed off the chip (on-chip-measurement guide §2.1):
its phase functions called tiny on the CPU mesh, its main() pinned to fail
without a TPU, and the compile-cache owner's placement rule."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from tpu_tfrecord import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """4 shards x 512 Criteo-shaped rows; 8 batches of 256."""
    root = tmp_path_factory.mktemp("chip_smoke")
    data = str(root / "criteo")
    rows = chip_smoke.write_dataset(data, seed=0, shards=4, rows_per_shard=512)
    return {"root": root, "data": data, "rows": rows, "batch": 256}


class TestPhasesOnTheCpuMesh:
    def test_build_requires_the_native_extension(self, monkeypatch):
        # never `clean` here: other xdist workers are using _lib/
        assert chip_smoke.phase_build(clean=False)["native_build_s"] >= 0
        from tpu_tfrecord import _native

        monkeypatch.setattr(_native, "available", lambda: False)
        with pytest.raises(chip_smoke.SmokeFailure, match="native extension"):
            chip_smoke.phase_build(clean=False)

    def test_dataset_has_the_shards_and_rows_asked_for(self, tiny):
        files = [f for f in os.listdir(tiny["data"]) if f.endswith(".tfrecord")]
        assert len(files) == 4 and tiny["rows"] == 2048

    def test_ingest_train(self, tiny):
        out = chip_smoke.phase_ingest_train(
            tiny["data"], tiny["rows"], tiny["batch"], vocab=4096, steps=8
        )
        assert out["steps"] == 8 and out["step_compile_s"] > 0

    def test_ingest_train_fails_on_a_wrong_row_count(self, tiny):
        with pytest.raises(chip_smoke.SmokeFailure, match="rows consumed"):
            chip_smoke.phase_ingest_train(
                tiny["data"], tiny["rows"] + 1, tiny["batch"], vocab=4096, steps=8
            )

    def test_compare_sparse_vs_dense_and_pallas_vs_xla(self, tiny):
        chip_smoke.phase_compare(
            tiny["data"], tiny["batch"], cmp_vocab=64, interpret=True
        )

    def test_compare_fails_on_a_kernel_that_returns_zeros(self, tiny, monkeypatch):
        """Most pair columns hold values far under the bf16 atol: the
        per-column bound is what catches a kernel that wrote nothing."""
        import jax.numpy as jnp

        from tpu_tfrecord.models import interaction

        def zeros(emb, **_):
            p = emb.shape[1] * (emb.shape[1] - 1) // 2
            return jnp.zeros((emb.shape[0], p), emb.dtype)

        monkeypatch.setattr(interaction, "dot_interaction_pallas", zeros)
        with pytest.raises(chip_smoke.SmokeFailure, match="pallas == XLA"):
            chip_smoke.phase_compare(
                tiny["data"], tiny["batch"], cmp_vocab=64, interpret=True
            )

    def test_resume(self, tiny):
        chip_smoke.phase_resume(
            tiny["data"], tiny["batch"], str(tiny["root"] / "input_state")
        )

    def test_transport_probe_reports_every_field(self):
        out = chip_smoke.phase_transport_probe(n=128, chain=2, h2d_mb=1)
        assert all(
            out[k] >= 0 for k in (
                "dispatch_s", "block_until_ready_s", "scalar_fetch_s",
                "h2d_dispatch_s", "h2d_complete_s",
            )
        )

    def test_multichip_phase_on_four_virtual_devices(self, monkeypatch):
        """At 'highest' only: on the CPU both of the phase's precisions are
        one float32 computation compiled under two cache keys, and 'highest'
        holds the tighter of the two bounds."""
        monkeypatch.setattr(chip_smoke, "MULTICHIP_TOL", {
            "highest": chip_smoke.MULTICHIP_TOL["highest"]})
        chip_smoke.phase_multichip(4, steps=2)

    def test_serving_one_stage_answers_like_the_sequential_reference(self, tiny):
        """S = 1 works: the replica CLI on a one-device pipe mesh."""
        wd = str(tiny["root"])
        chip_smoke.parent_serving(wd, seed=0, platform="cpu")
        chip_smoke.phase_serve_reference(wd)


class TestMainRefusesAnythingButTpu:
    def test_require_tpu_refuses_the_cpu(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="needs a TPU"):
            chip_smoke.require_tpu(1)

    def test_the_parent_imports_neither_jax_nor_bench(self):
        """A process that touched JAX holds the chip its children need, and
        the smoke's Criteo definition is examples/criteo.py, nothing larger."""
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys, chip_smoke; "
             "print(sorted({'jax', 'bench'} & set(sys.modules)), "
             "sys.modules['criteo'].__file__)"],
            cwd=REPO, capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        assert out == ["[]", os.path.join(REPO, "examples", "criteo.py")]

    @pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
    def test_main_exits_nonzero_under_jax_platforms_cpu(self, tmp_path, argv):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--workdir", str(tmp_path), *argv],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "needs a TPU" in proc.stderr
        # it refused before touching anything: no dataset, no build
        assert not os.path.exists(tmp_path / "criteo")


class TestCompileCacheOwner:
    """One owner, placed from outside (run in a child: jax.config is
    process-global and conftest keeps the cache off for the suite)."""

    SCRIPT = (
        "import json, jax\n"
        "from tpu_tfrecord import compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "got = compile_cache.enable()\n"
        "print(json.dumps({'before': before, 'returned': got,"
        " 'after': jax.config.jax_compilation_cache_dir}))\n"
    )

    def _run(self, env_extra):
        env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
        env.update(env_extra, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT], env=env, capture_output=True,
            text=True, timeout=120, check=True,
        ).stdout
        return json.loads(out.strip().splitlines()[-1])

    def test_variable_set_means_nothing_is_set_in_code(self, tmp_path):
        placed = str(tmp_path / "cc")
        got = self._run({compile_cache.ENV_VAR: placed})
        # jax read the variable by itself; enable() changed nothing
        assert got["before"] == got["after"] == got["returned"] == placed

    def test_variable_unset_means_the_fixed_path_in_the_checkout(self):
        got = self._run({})
        assert got["before"] is None
        assert got["after"] == got["returned"] == os.path.join(REPO, ".jax_cache")
        assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")

    def test_jax_cache_is_git_ignored_and_conftest_leaves_it_off(self):
        import jax

        with open(os.path.join(REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
        if compile_cache.ENV_VAR not in os.environ:
            assert jax.config.jax_compilation_cache_dir is None
