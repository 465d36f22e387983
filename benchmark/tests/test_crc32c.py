"""The benchmark's numpy CRC32C against the program's, on seeded strings."""

import numpy as np

from benchmark.harness.crc32c import crc32c_rows
from tpu_tfrecord import wire


def test_known_vector():
    assert int(crc32c_rows(np.frombuffer(b"123456789", np.uint8)[None])[0]) == 0xE3069283


def test_against_the_program_on_seeded_strings():
    rng = np.random.default_rng(20260927)
    for width in (1, 7, 8, 9, 31):
        data = rng.integers(0, 256, size=(200, width), dtype=np.uint8)
        want = [wire.crc32c(row.tobytes()) for row in data]
        assert crc32c_rows(data).tolist() == want
