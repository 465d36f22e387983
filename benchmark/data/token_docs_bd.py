"""The data shape ``token_docs_bd``: ``token_docs``'s shards for a
block-diffusion model, whose vocabulary holds a mask id that no document may
hold.

``data/token_docs.py`` draws a token from every id of the configuration's
vocabulary but 0 (the packer's end-of-document id). A diffusion model's input
pipeline replaces tokens by the mask id (``TokenPacker(noise=)``), and the
score reads a position as masked where the noised row holds that id, so a
document that held it would be scored at a position nobody noised. The mask
id is the vocabulary's LAST id (the mix's ``mask_id``, held to that here), and
this shape is ``token_docs``'s own functions over one id fewer: lengths by the
same law, ranks Zipf over the ``vocab_size - 2`` ids besides 0 and the mask
id, through the same seeded bijection of that many ids.
"""

from __future__ import annotations

from benchmark.data import token_docs
from benchmark.data.token_docs import describe, schema  # noqa: F401


def without_mask_id(cfg: dict, mix: dict) -> dict:
    """``cfg`` as ``token_docs`` reads it, its vocabulary one id shorter: the last
    id, the mask's, is out of every document's reach."""
    if mix["mask_id"] != cfg["vocab_size"] - 1:
        raise ValueError(f"the mask id {mix['mask_id']} has to be the vocabulary's last "
                         f"({cfg['vocab_size'] - 1}): the documents draw from the ids before it")
    return {**cfg, "vocab_size": cfg["vocab_size"] - 1}


def shard_docs(seed: int, shard: int, count: int, cfg: dict, mix: dict):
    return token_docs.shard_docs(seed, shard, count, without_mask_id(cfg, mix))


def write(data_dir: str, seed: int, cfg: dict, mix: dict) -> list:
    return token_docs.write(data_dir, seed, without_mask_id(cfg, mix), mix)
