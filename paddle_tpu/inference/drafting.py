"""Per-scenario speculative drafters for the serving engine.

The engine's built-in drafter (serving._ngram_draft) is one flat
prompt-lookup: a single n-gram length for every workload, which is why
the PERF.md decode A/B sits at ~0.49 acceptance — chat-style short
contexts rarely match a long n-gram and fall through to the
repeat-step-token fallback, while long-document contexts could support
a stricter (higher-precision) match than the flat default attempts.

This module feeds the loadgen *scenario label* into the engine's
pluggable ``drafter=`` hook: each scenario maps to an ordered n-gram
BACKOFF ladder (longest/most-precise first; lanes that fail a longer
lookup retry the shorter one before the repeat-token fallback). The
drafter stays pure jnp over the device-resident history buffer, so it
traces into the fused decode scan exactly like the built-in one, and
the committed stream is still byte-identical to non-speculative decode
— acceptance only changes how many drafts survive verification.

Two harnesses measured this, and they sit in very different regimes:

* round 11's decode A/B (repetitive tiled-motif prompts, 193 new
  tokens; its harness is gone): the flat drafter sat at ~0.48-0.49
  acceptance; the tuned (3,2)-ladder at depth 2 reached ~0.58, because
  the second rung
  converts fallback drafts (almost never accepted) into short-context
  matches and the shallower depth stops betting tokens past where the
  match decays.
* tools/loadgen.py --speculative (Weyl-sequence prompts, 4-12 token
  replies): absolute acceptance is intrinsically tiny (a chaotic tiny
  model emitting a handful of tokens gives prompt-lookup almost
  nothing to match), but the tuned rows still beat the flat drafter
  at equal depth and the report's per-scenario acceptance block makes
  the regime visible instead of hiding it in an aggregate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["SCENARIO_DRAFT_STATS", "backoff_drafter", "suffix_drafter",
           "scenario_drafter", "scenario_draft_depth"]

# scenario label -> n-gram statistics for the drafter. "ngrams" is the
# backoff ladder (tried longest-first per lane); "depth" the draft depth
# the harness configures the engine with. The entries are measured, not
# guessed — retune with tools/loadgen.py --speculative after touching
# the drafter or the harness model (PERF.md "auto-sharding + drafting"
# section records the current numbers).
SCENARIO_DRAFT_STATS = {
    "chat": {"ngrams": (3, 2), "depth": 2},
    "long_document": {"ngrams": (2,), "depth": 2},
    "offline_batch": {"ngrams": (3, 2), "depth": 2},
    "structured_output": {"ngrams": (2,), "depth": 2},
    # round 18: tenant-common system prompts give every lane a long
    # shared context — the suffix drafter (longest-match, not a fixed
    # ladder) exploits it; "suffix" selects it over the ngram ladder.
    # (3, 2) is measured, like every row here: deeper match caps LOSE
    # acceptance on the harness model (see suffix_drafter's docstring)
    "shared_prefix": {"suffix": (3, 2), "depth": 2},
}

# scenarios without a tuned row fall back to this ladder (strictly more
# capable than the engine's flat default: same primary, plus a rung,
# and a depth that stops betting past what short replies can accept)
_DEFAULT_STATS = {"ngrams": (3, 2), "depth": 2}


def _lookup(hist, lens, toks, depth, ngram):
    """One prompt-lookup rung: propose the `depth` tokens that followed
    the most recent earlier occurrence of the trailing `ngram`-token
    suffix; also return the per-lane matched mask so a backoff ladder
    can fall through. Mirrors serving._ngram_draft (including the
    cand + depth < n guard that keeps the continuation out of the
    previous step's rejected-draft leftovers)."""
    hmax = hist.shape[1]
    cand = jnp.arange(hmax)

    def one(h, n, t):
        ok = (cand >= ngram - 1) & (cand + depth < n)
        for gback in range(ngram):
            ok &= (h[jnp.clip(cand - gback, 0, hmax - 1)]
                   == h[jnp.clip(n - gback, 0, hmax - 1)])
        j = jnp.max(jnp.where(ok, cand, -1))
        cont = h[jnp.clip(j + 1 + jnp.arange(depth), 0, hmax - 1)]
        return jnp.where(j >= 0, cont, jnp.full((depth,), t)), j >= 0

    drafts, matched = jax.vmap(one)(hist, lens, toks)
    return drafts.astype(jnp.int32), matched


def backoff_drafter(ngrams):
    """Build a ``fn(hist, lens, toks, depth) -> [B, depth] int32``
    drafter that tries each n-gram length in order and keeps, per lane,
    the first rung that matched (unmatched lanes end at the repeat-
    step-token fallback the last rung produces)."""
    ladder = tuple(int(n) for n in ngrams)
    if not ladder or any(n < 1 for n in ladder):
        raise ValueError(f"n-gram ladder must be ints >= 1, got {ngrams!r}")

    def drafter(hist, lens, toks, depth):
        out = have = None
        for n in ladder:
            drafts, matched = _lookup(hist, lens, toks, depth, n)
            if out is None:
                out, have = drafts, matched
            else:
                out = jnp.where(have[:, None], out, drafts)
                have = have | matched
        return out

    drafter.label = "backoff:" + ",".join(str(n) for n in ladder)
    return drafter


def suffix_drafter(max_suffix=3, min_match=2):
    """Round 18: a suffix-automaton-style lookup drafter. Instead of a
    fixed n-gram ladder, each lane finds the earlier position whose
    context shares the LONGEST suffix (up to `max_suffix` tokens, at
    least `min_match`) with the current one and proposes the tokens
    that followed it — the device-parallel equivalent of walking a
    suffix automaton of (prompt + committed history) to its deepest
    state. The min_match floor keeps the short-context precision of
    the ladder's last rung; ties prefer the most recent occurrence.
    The max_suffix default is MEASURED on the bench decode A/B, not
    assumed: on the harness model, deeper caps monotonically lose
    acceptance (8 -> 0.548, 5 -> 0.572, 3 -> 0.597 at depth 2) because
    a chaotic small-vocab stream makes long coincidental matches
    outrank the recent short match that actually predicts — retune
    after touching the harness model. Same pure-jnp contract as
    backoff_drafter: traces into the fused scan, committed streams stay
    byte-identical, only acceptance moves."""
    M = int(max_suffix)
    lo = int(min_match)
    if not (1 <= lo <= M):
        raise ValueError(
            f"need 1 <= min_match <= max_suffix, got ({max_suffix!r}, "
            f"{min_match!r})")

    def drafter(hist, lens, toks, depth):
        hmax = hist.shape[1]
        cand = jnp.arange(hmax)

        def one(h, n, t):
            # h[n] is the step token (scattered by the caller). The
            # cand + depth < n guard keeps the continuation strictly in
            # the PAST (same reason as serving._ngram_draft: positions
            # >= n hold the previous step's rejected-draft leftovers).
            ok = cand + depth < n
            run = ok
            length = jnp.zeros(hmax, jnp.int32)
            for gback in range(M):
                run = (run & (cand - gback >= 0)
                       & (h[jnp.clip(cand - gback, 0, hmax - 1)]
                          == h[jnp.clip(n - gback, 0, hmax - 1)]))
                length = length + run.astype(jnp.int32)
            valid = ok & (length >= lo)
            # maximize (match length, recency): length majorizes, the
            # candidate index breaks ties toward the latest occurrence
            score = jnp.where(valid, length * hmax + cand, -1)
            j = jnp.argmax(score)
            cont = h[jnp.clip(j + 1 + jnp.arange(depth), 0, hmax - 1)]
            return jnp.where(score[j] >= 0, cont, jnp.full((depth,), t))

        return jax.vmap(one)(hist, lens, toks).astype(jnp.int32)

    drafter.label = f"suffix:{M},{lo}"
    return drafter


def scenario_drafter(scenario):
    """The per-scenario drafter for a loadgen scenario label (accepts a
    Scenario object or its name; unknown labels get the default
    ladder). The returned callable carries a ``label`` attribute the
    loadgen report surfaces next to the measured acceptance."""
    name = getattr(scenario, "name", scenario)
    stats = SCENARIO_DRAFT_STATS.get(str(name), _DEFAULT_STATS)
    if "suffix" in stats:
        fn = suffix_drafter(*stats["suffix"])
    else:
        fn = backoff_drafter(stats["ngrams"])
    fn.label = f"scenario:{name}:" + fn.label
    return fn


def scenario_draft_depth(scenario) -> int:
    """The tuned draft depth for a scenario label."""
    name = getattr(scenario, "name", scenario)
    return int(SCENARIO_DRAFT_STATS.get(str(name), _DEFAULT_STATS)["depth"])
