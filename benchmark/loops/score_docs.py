"""The ``score_docs`` loop: packed rows of token documents ->
``models.lm.score``, the step's output left on the device and fetched
``in_flight`` steps late; closed loop.

Every step's log-probabilities, sampled logits, expert counters and the
rows themselves are kept on the host (6 MB a step). Once the window has
closed, the first step's rows are scored again (bit-equal or not), the
program's parameters are freed, and the plain reference scores, document
by document and each alone, a sample of the window's steps drawn from the
seed with the first and the last in it, from the seed's weights and the
generator's documents. The comparison is of what the timed path produced
at the timed sizes.

What is compared, and why these numbers: the program's router reads
bfloat16 activations, so where a token's 8th and 9th expert score within
a rounding of each other it may choose the other one, and if either is
held here that token's hidden state moves by a gate's worth (about 1/8 of
one expert). A few tokens in a hundred do; their gaps are ten times the
typical one. Numbers that a handful of such tokens decide (a maximum)
would swing from seed to seed, so the comparison holds the typical gap
(median, 90th percentile), the root mean square, each document's mean (the
score a user ranks by), the sampled logits' root mean square, and the
typical gap over the first four positions of every document that follows
another in its row: where the packer meets the model.

None of those can tell a single layer's precision from the bfloat16
activations around it: the recurrent state kept in bfloat16 moves the
log-probabilities by a third of what the program's own rounding does. So
the step also returns two probes (``models.lm.score``: one head of the
first delta-rule layer's recurrence, seeded; the router's inputs and
choices at the sampled positions), and the model's ``probe_numbers`` holds
each to float64 on its own inputs, on the host, document by document: the recurrence
walked token by token from an empty state, the router's gates over all
320 experts.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import window
from benchmark.harness.token_feed import TokenFeed
from benchmark.loops.score import sampled_steps


def row_documents(tokens: np.ndarray, segs: np.ndarray):
    """[(row, start, tokens with the end id)] of a packed batch, and the
    rows whose ``segment_ids`` are not runs 1, 2, .. k then 0, whose pads
    are not the end id, or whose documents do not end in their one end id."""
    found, wrong = [], 0
    for r in range(tokens.shape[0]):
        t, s = tokens[r], segs[r]
        used = int((s != 0).sum())
        if not used:  # a row no bin reached (the flushed end of a stream): all pad
            wrong += 1 if s.any() or t.any() else 0
            continue
        cuts = np.flatnonzero(np.diff(s[:used])) + 1
        starts, ends = [0, *cuts], [*cuts, used]
        ids = [int(s[a]) for a in starts]
        sound = ids == list(range(1, len(ids) + 1)) and not s[used:].any() and not t[used:].any()
        for a, z in zip(starts, ends):
            sound = sound and t[z - 1] == 0 and bool(t[a:z - 1].all())
            found.append((r, a, t[a:z]))
        wrong += 0 if sound else 1
    return found, wrong


def expected_index(expected: list) -> dict:
    """{document's bytes: [its places in the epoch]}."""
    index = {}
    for i, doc in enumerate(expected):
        index.setdefault(doc.tobytes(), []).append(i)
    return index


def check_ingest(batches, expected: list) -> dict:
    """One epoch through reader -> packer -> prefetcher -> DeviceIterator,
    every batch fetched back: each segment of each row has to be one of the
    generator's documents, each document once."""
    index, seen = expected_index(expected), np.zeros(len(expected), np.int64)
    altered = wrong = rows = 0
    for gb in batches:
        docs, bad = row_documents(np.asarray(gb["tokens"]), np.asarray(gb["segment_ids"]))
        wrong, rows = wrong + bad, rows + gb["tokens"].shape[0]
        for _, _, doc in docs:
            places = index.get(doc[:-1].astype(np.int32).tobytes())
            if places is None:
                altered += 1
            else:  # twins share their count: spread it over them
                seen[places[int(np.argmin(seen[places]))]] += 1
    return {"docs_read": int(seen.sum()) + altered, "docs_written": len(expected),
            "tokens_altered": altered, "docs_missing": int((seen == 0).sum()),
            "docs_doubled": int((seen > 1).sum()), "segments_wrong": wrong, "rows": rows}


def sample_positions(seed: int, batch: int, row_tokens: int, count: int) -> np.ndarray:
    """[batch, count] sorted positions of a row whose full logits a step returns."""
    rng = np.random.default_rng([int(seed), 0x4C4F47])
    return np.stack([np.sort(rng.choice(row_tokens, size=count, replace=False))
                     for _ in range(batch)]).astype(np.int32)


HEAD = 4  # a document's first positions: where taps and state would reach back


def gaps(got_logprob: list, want_logprob: list, got_logits: np.ndarray,
         want_logits: np.ndarray, after_boundary: list) -> dict:
    """The compared numbers from per-document log-probabilities (nats) and the
    sampled logits, all sampled steps together. ``after_boundary[i]``: document
    i follows another in its row; the first ``HEAD`` positions of those are
    where a convolution's taps or a state that crossed the boundary would
    show, and a handful of positions in a thousand moves no other number."""
    each = [np.abs(np.asarray(g, np.float64) - w) for g, w in zip(got_logprob, want_logprob)]
    diff = np.concatenate(each)
    heads = [d[:HEAD] for d, after in zip(each, after_boundary) if after]
    return {
        "boundary_median_gap": float(np.median(np.concatenate(heads))) if heads else 0.0,
        "logprob_median_gap": float(np.median(diff)),
        "logprob_p90_gap": float(np.percentile(diff, 90.0)),
        "logprob_rms_gap": float(np.sqrt(np.mean(diff ** 2))),
        "doc_score_gap": float(max(abs(float(g.mean()) - float(w.mean()))
                                   for g, w in zip(got_logprob, want_logprob))),
        "logit_rms_gap": window.rms_gap(got_logits, want_logits),
    }


def compare_steps(env, kept: list, sample_at: np.ndarray, score_reference, probe_numbers) -> tuple:
    """(numbers, documents no generator made, documents compared) for the kept
    steps: the rows' documents looked up among the generator's, scored by
    ``score_reference(docs, logits_at)``, and held against what the step
    returned for them; the step's probes cut into documents for
    ``probe_numbers(scans, routed)``."""
    index = expected_index(env.expected)
    docs, logits_at, got_logprob, got_logits, after, strangers = [], [], [], [], [], 0
    scans, routed = [], []
    for step in kept:
        found, _ = row_documents(step["tokens"], step["segment_ids"])
        for r, start, doc in found:
            places = index.get(doc[:-1].astype(np.int32).tobytes())
            if places is None:
                strangers += 1
                continue
            n = len(doc) - 1
            inside = [int(s) for s, p in enumerate(sample_at[r]) if start <= p < start + n]
            docs.append(np.append(env.expected[places[0]], 0).astype(np.int32))
            logits_at.append([int(sample_at[r][s]) - start for s in inside])
            got_logprob.append(step["logprob"][r, start:start + n])
            got_logits.append(step["logits"][r, inside])
            after.append(start > 0)
            probes = step["probes"]
            scans.append({k: a[r, start:start + n] for k, a in probes["scan"].items()})
            routed.append({k: a[:, r, inside] for k, a in probes["router"].items()})
    want = score_reference(docs, logits_at)
    numbers = gaps(got_logprob, want["logprob"], np.concatenate(got_logits),
                   np.concatenate(want["logits"]), after)
    return {**numbers, **probe_numbers(scans, routed)}, strangers, len(docs)


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
    # the head of the first delta-rule layer whose recurrence every step returns
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
        kept.update(tokens=np.asarray(gb["tokens"]), segment_ids=np.asarray(gb["segment_ids"]))
        lm.record_moe_counters(kept["visits"], kept["dropped"])
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
    visits = np.array([s["visits"].sum(axis=1).mean() for s in scored])
    triangle, n_docs, positions = [], 0, []
    for s in scored:
        lengths = np.array([len(d) - 1 for _, _, d in row_documents(s["tokens"], s["segment_ids"])[0]])
        triangle.append(float((lengths * (lengths + 1) / 2).sum()))
        positions.append(int(lengths.sum()))
        n_docs += len(lengths)
    # what the window's rows held, for needs() (benchmark/models/solar_open2.py)
    cfg["observed"] = {"tokens": float(np.mean(positions)), "triangle": float(np.mean(triangle)),
                       "visits": float(visits.mean())}
    env.info("packed", window_tokens=int(used.sum()), documents=n_docs, pack_density=density,
             window_density=float(used.mean() / (batch * (row_tokens + 1))),
             visits_max_over_mean=max(float((s["visits"].max(axis=1) / s["visits"].mean(axis=1)).max())
                                      for s in scored), a_step=cfg["observed"])

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
        "moe_visits_dropped": float(sum(int(s["dropped"].sum()) for s in scored)),
        "steps_not_finite": float(failed),
    }
    env.info("reference", seconds=time.perf_counter() - t0, steps_compared=chosen,
             documents_compared=n_compared)
    measured.update(rows=steps * batch, batch=batch, attempted=steps, failed=failed)
    measured["correct"] = window.judge(env, compared, mix["limits"])
    return measured
