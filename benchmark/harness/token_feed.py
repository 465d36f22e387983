"""The input path of the token-document cells, on the program's normal path:

TFRecordDataset (native scan + CRC + decode of the ragged int64 ``tokens``
column, ``reader_batch`` documents a batch) -> TokenPacker
(bin packing: no document crosses a row) -> HostPrefetcher ->
DeviceIterator(transfer_thread) -> {"tokens", "segment_ids"} [B, L + 1] on
the device. A finite stream ends with the packer's open bins flushed, so an
epoch's walk sees every document.
"""

from __future__ import annotations

from benchmark.data import token_docs


class TokenFeed:
    def __init__(self, data_dir: str, mix: dict, mesh, num_epochs):
        from tpu_tfrecord.io.dataset import TFRecordDataset
        from tpu_tfrecord.tpu import DeviceIterator, HostPrefetcher
        from tpu_tfrecord.tpu.ingest import TokenPacker

        if mix["codec"] != "none":
            raise ValueError(f"codec {mix['codec']!r}: only uncompressed shards are written yet")
        ds = TFRecordDataset(
            data_dir, batch_size=mix["reader_batch"], schema=token_docs.schema(),
            prefetch=mix["prefetch"], num_epochs=num_epochs,
        )
        self.packer = TokenPacker(mix["batch"], mix["row_tokens"], packing=mix["packing"])
        self._batches = ds.batches()

        def ready():
            while (batch := self.packer.pop()) is not None:
                yield batch

        def host_batches():
            for cb in self._batches:
                self.packer.feed_column(cb["tokens"])
                yield from ready()
            self.packer.flush()
            yield from ready()

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
