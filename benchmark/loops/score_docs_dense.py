"""The ``score_docs_dense`` loop: ``score_docs`` for a pattern without an
expert layer.

Packed rows of token documents -> ``models.lm.score``, the step's output left
on the device and fetched ``in_flight`` steps late; closed loop. The timed
window, the ingest check, the repeat check, the sample of verified steps and
the judge are ``score_docs``'s own calls; what is compared is ``score_docs``'s
(its module docstring has why those numbers), less what only a router has.

It differs from ``loops/score_docs.py`` where that file reads an expert
layer's counters: a pattern without experts returns ``visits`` [0,
experts_held], ``dropped`` [0] and no ``probes["router"]`` (nothing is made up
for a loop's sake), so there is no load to record
(``lm.record_moe_counters``), no ``visits`` in what ``needs()`` is told of the
window's rows, no ``moe_visits_dropped`` and no ``router_gate_gap`` among the
numbers; the model's ``probe_numbers`` holds the one probed head of the first
recurrent layer to float64 on its own inputs. A program that does return
visits is refused: this loop would not look at them.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import window
from benchmark.harness.token_feed import TokenFeed
from benchmark.loops import score_docs
from benchmark.loops.score import sampled_steps
from benchmark.loops.score_docs import check_ingest, row_documents, sample_positions


def without_experts(out: dict) -> None:
    """A step's output has to be a pattern's without an expert layer: ``visits``
    and ``dropped`` empty, no router probed. Raises where it is not."""
    visits, dropped = np.asarray(out["visits"]), np.asarray(out["dropped"])
    if visits.size or dropped.size or "router" in out["probes"]:
        raise ValueError(
            f"the score_docs_dense loop is for a pattern without expert layers; this program returned visits "
            f"{visits.shape}, dropped {dropped.shape} and probes {sorted(out['probes'])}: use score_docs")


def compare_steps(env, kept: list, sample_at: np.ndarray, score_reference, probe_numbers) -> tuple:
    """``score_docs.compare_steps`` for steps whose program probed no router:
    it is handed each step with an empty record in the router's place (it cuts
    whatever the record holds per document: nothing), so ``probe_numbers``
    gets the recurrence's probes and an empty ``routed`` a document."""
    return score_docs.compare_steps(
        env, [{**step, "probes": {**step["probes"], "router": {}}} for step in kept], sample_at, score_reference,
        probe_numbers)


def run(env) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_tfrecord.models import lm

    cfg, mix, model, seed = env.cfg, env.mix, env.model, env.seed
    batch, row_tokens = mix["batch"], mix["row_tokens"]
    pcfg = model.program(cfg, mix)

    t0 = time.perf_counter()
    params = model.program_params(seed, cfg)
    jax.block_until_ready(params)
    env.info("state", seconds=time.perf_counter() - t0,
             param_bytes=int(sum(a.nbytes for a in jax.tree.leaves(params))))
    sample_at = sample_positions(seed, batch, row_tokens, mix["logit_samples"])
    sample_dev = jnp.asarray(sample_at)
    # the head of the first recurrent layer whose recurrence every step returns
    probe_head = jnp.int32(np.random.default_rng([int(seed), 0x50524F42]).integers(pcfg.kda_heads))
    step_j = jax.jit(lambda p, tokens, segs, at, head: lm.score(p, tokens, segs, at, pcfg, head))

    feed = TokenFeed(env.data_dir, mix, env.mesh, num_epochs=1)
    try:
        first = next(feed)
        step_c = window.timed_compile(env, "score", step_j, params, first["tokens"],
                                      first["segment_ids"], sample_dev, probe_head)

        def rewound():
            yield first
            yield from feed

        ingest = check_ingest(rewound(), env.expected)
    finally:
        feed.close()
    env.info("ingest", **ingest)

    fetched = []

    def one_step(gb):
        with env.spans.span("dispatch_step"):
            return gb, step_c(params, gb["tokens"], gb["segment_ids"], sample_dev, probe_head)

    def observe(pair):
        gb, out = pair
        kept = jax.tree.map(np.asarray, out)
        without_experts(kept)
        kept.update(tokens=np.asarray(gb["tokens"]), segment_ids=np.asarray(gb["segment_ids"]))
        fetched.append(kept)

    feed = TokenFeed(env.data_dir, mix, env.mesh, num_epochs=None)
    try:
        loop = window.StepLoop(feed, one_step, observe, env.spans, mix["in_flight"])
        for _ in range(mix["warmup_steps"]):
            loop.step()
        loop.drain()
        warm = len(fetched)
        measured = env.measure(loop)
        density = feed.packer.density()
    finally:
        feed.close()

    steps = measured["steps"]
    scored = fetched[warm: warm + steps]
    again = step_c(params, jnp.asarray(scored[0]["tokens"]), jnp.asarray(scored[0]["segment_ids"]),
                   sample_dev, probe_head)
    repeat = max(float(np.abs(np.asarray(again[k]) - scored[0][k]).max())
                 for k in ("logprob", "logits"))
    del params, again

    failed = sum(1 for s in scored if not (np.isfinite(s["logprob"]).all()
                                           and np.isfinite(s["logits"]).all()))
    used = np.array([(s["segment_ids"] != 0).sum() for s in scored])
    triangle, n_docs, positions = [], 0, []
    for s in scored:
        lengths = np.array([len(d) - 1 for _, _, d in row_documents(s["tokens"], s["segment_ids"])[0]])
        triangle.append(float((lengths * (lengths + 1) / 2).sum()))
        positions.append(int(lengths.sum()))
        n_docs += len(lengths)
    # what the window's rows held, for needs() (benchmark/models/olmo_hybrid.py)
    cfg["observed"] = {"tokens": float(np.mean(positions)), "triangle": float(np.mean(triangle))}
    env.info("packed", window_tokens=int(used.sum()), documents=n_docs, pack_density=density,
             window_density=float(used.mean() / (batch * (row_tokens + 1))), a_step=cfg["observed"])

    t0 = time.perf_counter()
    chosen = sampled_steps(seed, steps, mix["verify_batches"])
    weights = model.reference_weights(seed, cfg)
    numbers, strangers, n_compared = compare_steps(
        env, [scored[k] for k in chosen], sample_at,
        lambda docs, at: model.reference_score(cfg, docs, weights, at),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed))
    compared = {
        **numbers,
        "repeat_gap": repeat,
        "tokens_altered": float(ingest["tokens_altered"] + strangers),
        "docs_missing": float(ingest["docs_missing"]),
        "docs_doubled": float(ingest["docs_doubled"]),
        "segments_wrong": float(ingest["segments_wrong"]),
        "steps_not_finite": float(failed),
    }
    env.info("reference", seconds=time.perf_counter() - t0, steps_compared=chosen,
             documents_compared=n_compared)
    measured.update(rows=steps * batch, batch=batch, attempted=steps, failed=failed)
    measured["correct"] = window.judge(env, compared, mix["limits"])
    return measured
