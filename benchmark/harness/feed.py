"""The input path under test, assembled as chip_smoke.py assembles it:

TFRecordDataset (native scan + CRC + decode + fused hash, [B, 40] pack) ->
host_batch_from_columnar -> pack_mixed (20-bit wire) -> HostPrefetcher ->
DeviceIterator(transfer_thread) -> a wire batch on the device.
"""

from __future__ import annotations

from benchmark.harness import criteo_io


class Feed:
    def __init__(self, data_dir: str, mix: dict, mesh, num_epochs):
        from tpu_tfrecord.io.dataset import TFRecordDataset
        from tpu_tfrecord.tpu import (
            DeviceIterator, HostPrefetcher, host_batch_from_columnar, pack_mixed,
        )

        if mix["codec"] != "none":
            raise ValueError(f"codec {mix['codec']!r}: only uncompressed shards are written yet")
        hash_buckets, pack = criteo_io.criteo_reader_spec()
        ds = TFRecordDataset(
            data_dir, batch_size=mix["batch"], schema=criteo_io.criteo_read_schema(),
            prefetch=mix["prefetch"], hash_buckets=hash_buckets, pack=pack,
            num_epochs=num_epochs,
        )
        self._batches = ds.batches()

        def host_batches():
            for cb in self._batches:
                packed = host_batch_from_columnar(
                    cb, ds.schema, hash_buckets=hash_buckets, pack=pack
                )["packed"]
                yield {"wire": pack_mixed(packed, criteo_io.KEEP, criteo_io.CAT_BITS)}

        self._prefetcher = HostPrefetcher(host_batches())
        self._device = DeviceIterator(
            self._prefetcher, mesh, transfer_thread=mix["transfer_thread"]
        )

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._device)

    def close(self) -> None:
        self._device.close()
        self._prefetcher.close()
        self._batches.close()
