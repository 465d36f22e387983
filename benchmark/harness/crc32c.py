"""Table-driven CRC32C (Castagnoli, reflected 0x82F63B78) in numpy.

The benchmark's own copy of the hash the reader applies to categorical
values, so that the expected matrix is made without any code under test.
``benchmark/tests/test_crc32c.py`` holds it against ``tpu_tfrecord.wire``.
"""

from __future__ import annotations

import numpy as np


def _table() -> np.ndarray:
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, (t >> 1) ^ np.uint32(0x82F63B78), t >> 1)
    return t.astype(np.uint32)


TABLE = _table()


def crc32c_rows(data: np.ndarray) -> np.ndarray:
    """CRC32C of each row of a ``[n, width]`` uint8 matrix -> ``[n]`` uint32."""
    data = np.asarray(data, dtype=np.uint8)
    crc = np.full(data.shape[0], 0xFFFFFFFF, dtype=np.uint32)
    for j in range(data.shape[1]):
        crc = TABLE[(crc ^ data[:, j]) & np.uint32(0xFF)] ^ (crc >> np.uint32(8))
    return crc ^ np.uint32(0xFFFFFFFF)
