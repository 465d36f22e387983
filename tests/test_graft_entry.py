"""The driver entry points (``__graft_entry__``): the entry compiles and runs,
and the multichip dry run walks its sharded step on 1 to 8 virtual devices
(from 2 up as two ``jax.distributed`` processes: its default)."""

import jax
import numpy as np
import pytest


class TestGraftEntry:
    def test_entry_compiles_and_runs(self):
        import __graft_entry__ as ge

        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (32,)
        assert np.isfinite(np.asarray(out)).all()

    @pytest.mark.parametrize("n", [8, 4, 2, 1])
    def test_dryrun_multichip(self, n):
        import __graft_entry__ as ge

        ge.dryrun_multichip(n)
