"""The models that consume the ingestion pipeline: what runs on the chip.

The reference framework ships no models (SURVEY.md §2: model-side parallelism
N/A) — its output is consumed by TensorFlow training jobs. Two families run
in the benchmark's cells (``BENCHMARK.json``), the rest in tests and examples:

- ``dlrm``: a Criteo-style DLRM (batch on 'data', embedding tables and
  hidden layers on 'model', padded sequence features on 'seq'), with
  ``interaction`` its dot-interaction: the two Criteo cells.
- the pattern model, the eight token cells (``solar_open2_ep8``,
  ``kimi_vl_a3b_lm``, ``deepseek_v32_exp_ep16``, ``trinity_large_ep8``,
  ``gigachat35_ep16``, ``nemotron_twotower_ep2``, ``olmo_hybrid_7b_pp4``,
  ``sdar_30b_a3b_pp8``):
  ``lm.PatternLMConfig`` holds the layer pattern as data (``gqa | swa | mla |
  kda | gdn | ssm | bda`` mixers, the sixth a state-space layer, the seventh
  softmax attention under block diffusion's mask, two streams a row, scored
  without a shift from a row and its noised copy; a ``gdn`` head's
  keys and values of their own widths; dense or expert feed-forward parts by
  layer, or no expert anywhere; norms before a branch, on it, or both; a
  layer may be ONE branch, a mixer or a feed-forward part alone, the other
  pattern saying ``"none"``) and ``lm.score`` scores packed documents. Its pieces: ``attention.flash_attention_widths`` (the one
  softmax kernel, on a TPU) and ``attention.blockwise_attention``
  (elsewhere); ``linear_attn`` (the delta rule and the state-space
  recurrence in chunks, a kernel each on a TPU); ``sparse_attn`` (YaRN's
  blend, the indexer's exact top-k); ``moe.route_top_k`` and
  ``moe.held_experts_apply`` (a chip's share of the experts, their unit of
  three matrices or of two); ``head`` (log p(next token): on a TPU one kernel
  that keeps the float32 logits in VMEM a tile at a time, blocks of them
  elsewhere). Its plain float32 references are the benchmark's, one an
  architecture (``benchmark/models/<name>.py``); the package holds none.
- in no cell: ``long_doc`` (a long-document classifier, ring or Ulysses
  attention over 'seq'), ``moe.moe_apply`` / ``moe_apply_ep`` (Switch-style
  experts sharded over a mesh axis), ``pipeline`` (GPipe over 'pipe') and
  the homogeneous ``lm.LMConfig`` trainer that joins them in one jitted,
  checkpointed train step (examples/train_lm.py; ``lm.LMStream`` serves it,
  examples/serve_lm.py).

Together the families exercise dp, tp, sp, ep, and pp on one mesh design
(all five run inside ``__graft_entry__.dryrun_multichip``).

The package-level flat names (init_params/forward/train_step/...) are the
DLRM family's, kept for compatibility; each family's full API lives on its
module (``models.dlrm``, ``models.long_doc``) — use those when working
with a specific family, the function names intentionally mirror each
other.
"""

from tpu_tfrecord.models import dlrm, lm, long_doc, moe, pipeline
from tpu_tfrecord.models.dlrm import (
    DLRMConfig,
    SparseEmbOptState,
    forward,
    init_params,
    loss_fn,
    make_synthetic_batch,
    param_shardings,
    sparse_opt_init,
    sparse_train_step,
    train_step,
)

__all__ = [
    "dlrm",
    "lm",
    "long_doc",
    "moe",
    "pipeline",
    "DLRMConfig",
    "init_params",
    "forward",
    "loss_fn",
    "train_step",
    "SparseEmbOptState",
    "sparse_opt_init",
    "sparse_train_step",
    "param_shardings",
    "make_synthetic_batch",
]
