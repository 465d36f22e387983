"""The input path of a block-diffusion cell: ``token_feed.py``'s, with a packer
that noises.

TFRecordDataset -> TokenPacker(noise=(block length, mask id, seed)) ->
HostPrefetcher -> DeviceIterator(transfer_thread) -> {"tokens", "segment_ids",
"noised", "noise_level"} [B, L + 1] on the device: beside the clean row the row
with a seeded share of each block's tokens replaced by the mask id, and each
token's block's noise level (``tpu_tfrecord.tpu.ingest.TokenPacker`` has the
law). The mix names the block length and the mask id; the draw's seed is the
run's. ``token_feed.TokenFeed`` builds its packer itself and is not this
PR's to edit, so the wiring is written out again here.
"""

from __future__ import annotations

from benchmark.data import token_docs


class NoisedTokenFeed:
    def __init__(self, data_dir: str, mix: dict, mesh, num_epochs, seed: int):
        from tpu_tfrecord.io.dataset import TFRecordDataset
        from tpu_tfrecord.tpu import DeviceIterator, HostPrefetcher
        from tpu_tfrecord.tpu.ingest import TokenPacker

        if mix["codec"] != "none":
            raise ValueError(f"codec {mix['codec']!r}: only uncompressed shards are written yet")
        ds = TFRecordDataset(
            data_dir, batch_size=mix["reader_batch"], schema=token_docs.schema(),
            prefetch=mix["prefetch"], num_epochs=num_epochs,
        )
        self.packer = TokenPacker(mix["batch"], mix["row_tokens"], packing=mix["packing"],
                                  noise=(mix["block_length"], mix["mask_id"], int(seed)))
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
