"""The ``score_docs_bd`` loop: ``score_docs`` for a block-diffusion model.

Packed rows of token documents AND their noised copies -> ``models.lm.score``
(two streams through every layer under a block mask), the step's output left
on the device and fetched ``in_flight`` steps late; closed loop. The timed
window, the repeat check, the sample of verified steps and the judge are
``score_docs``'s own calls (``loops/score_docs.py`` takes two columns and a
shifted score and is not this PR's to edit; what can be imported from it is).

It differs where the mechanism does:

- the feed is ``harness/token_feed_bd.NoisedTokenFeed``: four columns a batch,
  ``tokens``, ``segment_ids``, ``noised`` and ``noise_level`` [B, L + 1]; the
  packer starts every document at a whole multiple of the block length in its
  row, so a row's segments are runs with pads BETWEEN them, and a row's last
  column, which the model never reads, holds a pad or the end id of a
  document that fills its row (:func:`row_documents`; that document is scored
  without its end id);
- the ingest check holds all four: a segment is one of the generator's
  documents, a noised token is its clean token or the mask id (and the mask id
  nowhere in a clean row), ``noise_level`` is one number in (0, 1] a block of a
  document and 0 on pads (:func:`check_ingest`);
- the score is NOT shifted and exists at masked positions only: the gaps
  ``score_docs`` holds are read there (median, 90th percentile, root mean
  square; the first four positions of documents that follow another in their
  row), and a document's number is its BOUND a token, ``sum over blocks of
  (1 / t) sum over its masked positions of -logprob``, over its length
  (:func:`gaps`);
- the plain reference is handed each document's own noised copy, cut out of
  the kept rows, so the comparison needs no agreement of generators; that the
  feed's noise obeys its law is held apart, over every step of the window
  (:func:`noise_numbers`: ``noise_off_law``);
- the model's ``probe_numbers`` holds the router (softmax gates in float64)
  and one head of the first layer's attention on its own inputs under the mask.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import window
from benchmark.harness.token_feed_bd import NoisedTokenFeed
from benchmark.loops.score import sampled_steps
from benchmark.loops.score_docs import HEAD, expected_index, sample_positions


def row_documents(tokens: np.ndarray, segs: np.ndarray, block: int):
    """[(row, start, tokens with the end id)] of a packed batch of a noising
    packer, and the rows that are not what it owes: ``segment_ids`` runs 1, 2,
    .. k in order with pads (id 0, the end id) between and after them, every
    run starting at a whole multiple of ``block``, fewer than ``block`` pads
    between two runs, a document ending in its one end id, the last column a
    pad or an end id."""
    found, wrong = [], 0
    for r in range(tokens.shape[0]):
        t, s = tokens[r], segs[r]
        edges = np.flatnonzero(np.diff(np.concatenate([[0], s, [0]])))
        # a change of id inside documents is both one's end and the next's start
        starts = [a for a in edges if a < len(s) and s[a] != 0]
        ends = [z for z in edges if z > 0 and s[z - 1] != 0]
        sound = [int(s[a]) for a in starts] == list(range(1, len(starts) + 1)) and t[-1] == 0
        sound = sound and not t[s == 0].any()
        for i, (a, z) in enumerate(zip(starts, ends)):
            sound = sound and a % block == 0 and t[z - 1] == 0 and bool(t[a:z - 1].all())
            sound = sound and (i == 0 and a == 0 or i > 0 and 0 <= a - ends[i - 1] < block)
            found.append((r, int(a), t[a:z]))
        wrong += 0 if sound else 1
    return found, wrong


def noise_sound(doc: np.ndarray, noised: np.ndarray, level: np.ndarray, mask_id: int, block: int) -> bool:
    """One document's noised copy and levels are what the law allows: a noised
    token its clean token or the mask id, no mask id in the clean document,
    the level one number in (0, 1] a block counted from the document's first
    token."""
    by_block = np.repeat(level[::block], block)[: len(doc)]
    return bool(((noised == doc) | (noised == mask_id)).all() and not (doc == mask_id).any()
                and (level == by_block).all() and (level > 0).all() and (level <= 1).all())


def check_ingest(batches, expected: list, mask_id: int, block: int) -> dict:
    """One epoch through reader -> noising packer -> prefetcher ->
    DeviceIterator, every batch fetched back: each segment of each row has to
    be one of the generator's documents, each document once, its noised copy
    and levels sound (:func:`noise_sound`), pads clean in all four columns."""
    index, seen = expected_index(expected), np.zeros(len(expected), np.int64)
    altered = wrong = rows = 0
    for gb in batches:
        gb = {k: np.asarray(a) for k, a in gb.items()}
        docs, bad = row_documents(gb["tokens"], gb["segment_ids"], block)
        pads = gb["segment_ids"] == 0
        bad += int((gb["noised"][pads].any() or gb["noise_level"][pads].any()))
        wrong, rows = wrong + bad, rows + gb["tokens"].shape[0]
        for r, a, doc in docs:
            places = index.get(doc[:-1].astype(np.int32).tobytes())
            z = a + len(doc)
            if places is None or not noise_sound(doc, gb["noised"][r, a:z], gb["noise_level"][r, a:z],
                                                 mask_id, block):
                altered += 1
            else:  # twins share their count: spread it over them
                seen[places[int(np.argmin(seen[places]))]] += 1
    return {"docs_read": int(seen.sum()) + altered, "docs_written": len(expected),
            "tokens_altered": altered, "docs_missing": int((seen == 0).sum()),
            "docs_doubled": int((seen > 1).sum()), "segments_wrong": wrong, "rows": rows}


def noise_numbers(steps: list, mask_id: int, block: int) -> dict:
    """The feed's noise against its law, over every real token of ``steps``
    (kept rows: ``segment_ids``, ``noised``, ``noise_level``): the largest of
    four deviations, each in its own standard deviations under the law.

    A token of a block at level ``t`` is masked with probability ``t``,
    independently, and ``t`` ~ U(0, 1] a block. So (1) the window's masked
    count against the sum of its tokens' ``t``, beyond what a sum of
    Bernoullis allows (variance ``sum t (1 - t)``); (2) the same with every
    token weighed by ``t - 1/2``, which a noise that ignores ``t`` cannot meet
    (a flat half masks the right COUNT: ``sum (m - t)(t - 1/2)`` then reads
    ``-N / 12``); (3) and (4) the blocks' ``t`` against U(0, 1]'s mean 1/2
    and variance 1/12. Under the law each is about normal(0, 1): the limit is 6."""
    masked, level, firsts = [], [], []
    for s in steps:
        real = s["segment_ids"] != 0
        masked.append((s["noised"][real] == mask_id).astype(np.float64))
        level.append(s["noise_level"][real].astype(np.float64))
        # a block's level once: at the tokens that start a block of their document
        at = np.arange(real.shape[1])[None, :]
        begins = np.concatenate([np.ones_like(real[:, :1]),
                                 s["segment_ids"][:, 1:] != s["segment_ids"][:, :-1]], axis=1) & real
        first = np.maximum.accumulate(np.where(begins, at, 0), axis=1)
        firsts.append(s["noise_level"][real & ((at - first) % block == 0)].astype(np.float64))
    m, t, blocks = np.concatenate(masked), np.concatenate(level), np.concatenate(firsts)
    if not len(t):
        return {"noise_off_law": float("inf")}
    count = abs(float((m - t).sum())) / max(float(np.sqrt((t * (1 - t)).sum())), 1e-12)
    slope = abs(float(((m - t) * (t - 0.5)).sum())) / max(
        float(np.sqrt((t * (1 - t) * (t - 0.5) ** 2).sum())), 1e-12)
    n = len(blocks)
    mean = abs(float(blocks.mean()) - 0.5) * np.sqrt(12.0 * n)
    spread = abs(float(((blocks - 0.5) ** 2).mean()) - 1.0 / 12.0) * np.sqrt(180.0 * n)
    return {"noise_off_law": float(max(count, slope, mean, spread))}


def bound(logprob: np.ndarray, masked: np.ndarray, level: np.ndarray) -> float:
    """A document's likelihood bound a token: ``sum over its masked positions of
    -logprob / t`` (a position's ``t`` its block's) over its length."""
    return float((-np.asarray(logprob, np.float64)[masked] / level[masked].astype(np.float64)).sum()
                 / max(len(masked), 1))


def gaps(got_logprob: list, want_logprob: list, masked: list, level: list, got_logits: np.ndarray,
         want_logits: np.ndarray, after_boundary: list) -> dict:
    """The compared numbers from per-document log-probabilities (nats; every
    position's, read where ``masked[i]`` says the noised row held the mask id)
    and the sampled logits, all sampled steps together: ``score_docs.gaps``'s
    numbers at masked positions, a document's number its bound a token
    (:func:`bound`). ``after_boundary[i]``: document i follows another in its
    row; the masked ones of its first ``HEAD`` positions are where a block
    counted from the row, or a position carried over, would show first."""
    each = [np.abs(np.asarray(g, np.float64) - w) for g, w in zip(got_logprob, want_logprob)]
    diff = np.concatenate([d[m] for d, m in zip(each, masked)])
    heads = [d[:HEAD][m[:HEAD]] for d, m, after in zip(each, masked, after_boundary) if after]
    heads = np.concatenate(heads) if heads else np.zeros(0)
    if not len(diff):
        raise ValueError("no masked position among the verified documents: nothing was scored")
    return {
        "boundary_median_gap": float(np.median(heads)) if len(heads) else 0.0,
        "logprob_median_gap": float(np.median(diff)),
        "logprob_p90_gap": float(np.percentile(diff, 90.0)),
        "logprob_rms_gap": float(np.sqrt(np.mean(diff ** 2))),
        "doc_score_gap": float(max(abs(bound(g, m, t) - bound(w, m, t))
                                   for g, w, m, t in zip(got_logprob, want_logprob, masked, level))),
        "logit_rms_gap": window.rms_gap(got_logits, want_logits),
    }


def compare_steps(env, kept: list, sample_at: np.ndarray, score_reference, probe_numbers) -> tuple:
    """(numbers, documents no generator made, documents compared) for the kept
    steps: the rows' documents looked up among the generator's and scored by
    ``score_reference(docs, noised copies, logits_at)`` from their own noised
    copies as the feed made them, held against what the step returned for
    them; the step's probes cut into documents for ``probe_numbers(scans, routed)``."""
    mask_id, block = env.mix["mask_id"], env.mix["block_length"]
    index = expected_index(env.expected)
    docs, copies, logits_at, got_logprob, got_logits, after, strangers = [], [], [], [], [], [], 0
    masked, level, scans, routed = [], [], [], []
    for step in kept:
        found, _ = row_documents(step["tokens"], step["segment_ids"], block)
        for r, start, doc in found:
            places = index.get(doc[:-1].astype(np.int32).tobytes())
            if places is None:
                strangers += 1
                continue
            n = min(len(doc), step["logprob"].shape[1] - start)  # the columns the model reads
            inside = [int(s) for s, p in enumerate(sample_at[r]) if start <= p < start + n]
            docs.append(np.append(env.expected[places[0]], 0).astype(np.int32)[:n])
            copies.append(step["noised"][r, start:start + n].astype(np.int32))
            masked.append(copies[-1] == mask_id)
            level.append(step["noise_level"][r, start:start + n])
            logits_at.append([int(sample_at[r][s]) - start for s in inside])
            got_logprob.append(step["logprob"][r, start:start + n])
            got_logits.append(step["logits"][r, inside])
            after.append(start > 0)
            probes = step["probes"]
            scans.append({k: a[r, start:start + n] for k, a in probes["scan"].items()})
            routed.append({k: a[:, r, inside] for k, a in probes["router"].items()})
    want = score_reference(docs, copies, logits_at)
    numbers = gaps(got_logprob, want["logprob"], masked, level, np.concatenate(got_logits),
                   np.concatenate(want["logits"]), after)
    return {**numbers, **probe_numbers(scans, routed)}, strangers, len(docs)


def peak_bytes(env) -> int:
    """The device's ``peak_bytes_in_use`` so far (what ``run.py`` reads after the
    window as ``memory_peak_bytes``), so that a run says which phase set it."""
    return int((env.device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def run(env) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.models.sdar_moe import seen_pairs
    from tpu_tfrecord.models import lm

    cfg, mix, model, seed = env.cfg, env.mix, env.model, env.seed
    batch, row_tokens = mix["batch"], mix["row_tokens"]
    mask_id, block = mix["mask_id"], mix["block_length"]
    pcfg = model.program(cfg, mix)

    t0 = time.perf_counter()
    params = model.program_params(seed, cfg)
    jax.block_until_ready(params)
    env.info("state", seconds=time.perf_counter() - t0,
             param_bytes=int(sum(a.nbytes for a in jax.tree.leaves(params))))
    peaks = {"state": peak_bytes(env)}
    sample_at = sample_positions(seed, batch, row_tokens, mix["logit_samples"])
    sample_dev = jnp.asarray(sample_at)
    # the head of the first layer whose attention every step returns on its own inputs
    probe_head = jnp.int32(np.random.default_rng([int(seed), 0x50524F42]).integers(pcfg.n_heads))
    step_j = jax.jit(lambda p, tokens, segs, noised, at, head: lm.score(
        p, tokens, segs, at, pcfg, head, noised))
    columns = ("tokens", "segment_ids", "noised")

    feed = NoisedTokenFeed(env.data_dir, mix, env.mesh, 1, seed)
    try:
        first = next(feed)
        step_c = window.timed_compile(env, "score", step_j, params, *(first[c] for c in columns),
                                      sample_dev, probe_head)
        peaks["compile"] = peak_bytes(env)

        def rewound():
            yield first
            yield from feed

        ingest = check_ingest(rewound(), env.expected, mask_id, block)
    finally:
        feed.close()
    env.info("ingest", **ingest)
    peaks["ingest"] = peak_bytes(env)

    fetched = []

    def one_step(gb):
        with env.spans.span("dispatch_step"):
            return gb, step_c(params, *(gb[c] for c in columns), sample_dev, probe_head)

    def observe(pair):
        gb, out = pair
        kept = jax.tree.map(np.asarray, out)
        kept.update({c: np.asarray(gb[c]) for c in (*columns, "noise_level")})
        lm.record_moe_counters(kept["visits"], kept["dropped"])
        fetched.append(kept)

    feed = NoisedTokenFeed(env.data_dir, mix, env.mesh, None, seed)
    try:
        loop = window.StepLoop(feed, one_step, observe, env.spans, mix["in_flight"])
        for _ in range(mix["warmup_steps"]):
            loop.step()
        loop.drain()
        warm = len(fetched)
        env.info("memory_peak", **peaks, warmup=peak_bytes(env))
        measured = env.measure(loop)
        density = feed.packer.density()
    finally:
        feed.close()

    steps = measured["steps"]
    scored = fetched[warm: warm + steps]
    again = step_c(params, *(jnp.asarray(scored[0][c]) for c in columns), sample_dev, probe_head)
    repeat = max(float(np.abs(np.asarray(again[k]) - scored[0][k]).max())
                 for k in ("logprob", "logits"))
    del params, again

    failed = sum(1 for s in scored if not (np.isfinite(s["logprob"]).all()
                                           and np.isfinite(s["logits"]).all()))
    used = np.array([(s["segment_ids"][:, :-1] != 0).sum() for s in scored])
    visits = np.array([s["visits"].sum(axis=1).mean() for s in scored])
    pairs, n_docs, holds = [], 0, []
    for s in scored:
        lengths = [min(len(d), row_tokens - a) for _, a, d in row_documents(s["tokens"], s["segment_ids"], block)[0]]
        pairs.append(float(sum(seen_pairs(n, block) for n in lengths)))
        n_docs += len(lengths)
        holds.append(int(((s["noised"] == mask_id) & (s["segment_ids"] != 0))[:, :-1].sum()))
    # what the window's rows held, for needs() (benchmark/models/sdar_moe.py)
    cfg["observed"] = {"tokens": float(used.mean()), "pairs": float(np.mean(pairs)),
                       "masked": float(np.mean(holds)), "visits": float(visits.mean())}
    env.info("packed", window_tokens=int(used.sum()), documents=n_docs, pack_density=density,
             window_density=float(used.mean() / (batch * (row_tokens + 1))),
             masked_share=float(np.sum(holds) / max(used.sum(), 1)),
             visits_max_over_mean=max(float((s["visits"].max(axis=1) / s["visits"].mean(axis=1)).max())
                                      for s in scored), a_step=cfg["observed"])

    t0 = time.perf_counter()
    chosen = sampled_steps(seed, steps, mix["verify_batches"])
    weights = model.reference_weights(seed, cfg)
    numbers, strangers, n_compared = compare_steps(
        env, [scored[k] for k in chosen], sample_at,
        lambda docs, copies, at: model.reference_score(cfg, docs, weights, at, noised=copies,
                                                       block_length=block),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed, block))
    compared = {
        **numbers,
        **noise_numbers(scored, mask_id, block),
        "repeat_gap": repeat,
        "tokens_altered": float(ingest["tokens_altered"] + strangers),
        "docs_missing": float(ingest["docs_missing"]),
        "docs_doubled": float(ingest["docs_doubled"]),
        # the ingest walk's rows, and the window's own as the step saw them (a start off a whole block)
        "segments_wrong": float(ingest["segments_wrong"] + sum(int(s["starts_off_block"]) for s in scored)),
        "moe_visits_dropped": float(sum(int(s["dropped"].sum()) for s in scored)),
        "steps_not_finite": float(failed),
    }
    env.info("reference", seconds=time.perf_counter() - t0, steps_compared=chosen,
             documents_compared=n_compared)
    measured.update(rows=steps * batch, batch=batch, attempted=steps, failed=failed)
    measured["correct"] = window.judge(env, compared, mix["limits"])
    return measured
