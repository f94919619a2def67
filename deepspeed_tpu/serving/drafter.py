"""Draft-token proposers for speculative decoding.

The engine's speculative tick needs K-1 cheap draft tokens per active
slot per round; the target model then verifies the whole window in ONE
multi-query paged-attention dispatch (adapters ``verify``) and greedy
accept/reject keeps outputs token-for-token identical to the plain
engine. Two proposers:

- ``NGramDrafter`` — self-drafting / prompt-lookup: match the request's
  trailing n-gram against its own history (prompt + generated) and
  propose the continuation of the most recent earlier occurrence. Pure
  host numpy, no second checkpoint, no device work — the
  default. Wins exactly when generation is repetitive (greedy decode
  loops, structured output, quote-the-prompt tasks); on novel text the
  accept rate collapses toward 0 and each round degenerates to one
  committed token per verify call (see docs/serving.md for when that
  still breaks even).
- ``ModelDrafter`` — a small drafter MODEL (e.g. a GPT-2-small config)
  served through its OWN adapter + paged cache, drafting K-1 greedy
  tokens through the existing multi-step tick machinery. Rollback after
  a rejection is a pointer move: the drafter's cache rows for the
  accepted span were produced by the same fed tokens as the target's,
  so its ``pos`` simply rewinds to the target's committed position and
  stale rows are overwritten by the next round's appends.
"""

from typing import List, Optional

import numpy as np
import jax


def _realign_restored(drafter, slot: int, prompt: np.ndarray,
                      generated: List[int], total_tokens: int) -> None:
    """ONE restore-realignment rule for both drafters: admit the prompt
    with the first committed token, then feed the remaining committed
    tokens through observe_plain (generated[:-1] fed -> generated[1:]
    committed — the engine's own feed/commit alignment, so the
    drafter's pos lands at S + len(generated) - 1 exactly like the
    engine slot's)."""
    prompt = np.asarray(prompt, np.int32)  # sync-ok: host token list
    gen = [int(t) for t in generated]
    assert gen, "a restored slot always holds the prefill-sampled token"
    drafter.admit(slot, prompt, gen[0], total_tokens)
    feed_all = gen[:-1]
    committed_all = gen[1:]
    B = len(getattr(drafter, "pos", getattr(drafter, "_hist", [])))
    off = 0
    while off < len(feed_all):
        # pow2 chunks (largest-first decomposition, capped at the
        # engine's tick ceiling): a ModelDrafter's observe_plain
        # compiles one verify program per distinct row count, and the
        # tick/verify paths only ever dispatch pow2 rows — an
        # arbitrary-length realign here would compile a fresh program
        # per restored progress value, right on the restore hot path
        n = 32
        while n > len(feed_all) - off:
            n //= 2
        cols_feed = np.zeros((n, B), np.int32)
        cols_committed = np.zeros((n, B), np.int32)
        cols_feed[:, slot] = np.asarray(       # sync-ok: host lists
            feed_all[off:off + n], np.int32)
        cols_committed[:, slot] = np.asarray(  # sync-ok: host lists
            committed_all[off:off + n], np.int32)
        drafter.observe_plain([slot], cols_feed, cols_committed)
        off += n


class NGramDrafter:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the request's trailing n-gram."""

    aligned = False   # no drafter-side KV state: the engine may commit
    #                   the free correction token on an all-accept round

    def __init__(self, slots: int, ngram_max: int = 3,
                 ngram_min: int = 1):
        assert ngram_max >= ngram_min >= 1, (ngram_max, ngram_min)
        self.ngram_max = ngram_max
        self.ngram_min = ngram_min
        self._hist: List[Optional[np.ndarray]] = [None] * slots

    # -- slot lifecycle (host bookkeeping only) ---------------------------

    def admit(self, slot: int, prompt: np.ndarray,
              first_tok: int, total_tokens: int) -> None:
        self._hist[slot] = np.append(  # sync-ok: prompt is a host
            np.asarray(prompt, np.int32), np.int32(first_tok))  # array

    def release(self, slot: int) -> None:
        self._hist[slot] = None

    def commit(self, slot: int, committed: List[int], new_pos: int,
               last_tok: int) -> None:
        """Append the verifier's committed tokens to the slot history
        (the drafts were speculative — only what the target accepted
        becomes context for the next round)."""
        self._hist[slot] = np.append(  # sync-ok: committed is a host
            self._hist[slot], np.asarray(committed, np.int32))  # list

    def observe_plain(self, slots: List[int], feed: np.ndarray,
                      committed: np.ndarray) -> None:
        """The engine committed ``committed[:, s]`` tokens per slot in a
        PLAIN (non-speculative) tick — history-only realignment here."""
        for s in slots:
            self._hist[s] = np.append(  # sync-ok: host arrays
                self._hist[s], np.asarray(committed[:, s], np.int32))

    def _propose(self, h: np.ndarray, k: int) -> np.ndarray:
        L = len(h)
        for n in range(min(self.ngram_max, L - 1), self.ngram_min - 1,
                       -1):
            pat = h[L - n:]
            if L - 1 < n:
                continue
            win = np.lib.stride_tricks.sliding_window_view(h[:L - 1], n)
            hits = np.nonzero((win == pat).all(axis=1))[0]
            if len(hits):
                i = int(hits[-1])
                cont = h[i + n:i + n + k]
                if len(cont) < k:
                    cont = np.concatenate(
                        [cont, np.full(k - len(cont), h[-1], np.int32)])
                return cont.astype(np.int32)
        return np.full(k, h[-1], np.int32)   # cold: repeat last token

    def draft(self, active_slots: List[int], k: int) -> np.ndarray:
        """[slots..., k] draft tokens for the given active slots (rows
        align with ``active_slots`` order)."""
        return np.stack([self._propose(self._hist[s], k)
                         for s in active_slots])

    def restore_slot(self, slot: int, prompt: np.ndarray,
                     generated: List[int], total_tokens: int) -> None:
        """Realign after an elastic restore (ISSUE 11): the slot's
        committed stream is ``prompt + generated`` and the drafter saw
        none of it — ``admit`` + the existing ``observe_plain``
        contract rebuild exactly the state an uninterrupted run would
        hold (for a ModelDrafter that includes the K/V rows, fed
        through one teacher-forcing verify dispatch)."""
        _realign_restored(self, slot, prompt, generated, total_tokens)


class ModelDrafter:
    """A second (smaller) serving adapter drafting greedy tokens
    through its own paged cache. The drafter's pool is always fully
    provisioned (``num_blocks=0`` default geometry), so its admission
    can never fail after the target's succeeded."""

    aligned = True    # KV state: commits are capped at the drafted rows
    #                   so the drafter cache never claims unwritten rows

    def __init__(self, adapter):
        self.adapter = adapter
        self.cache = adapter.make_cache()
        slots = adapter.spec.slots
        self.pos = np.full(slots, -1, np.int64)
        self.last = np.zeros(slots, np.int64)
        # drafting is greedy-only: no rng anywhere in this class
        self._temps = np.zeros(slots, np.float32)

    def admit(self, slot: int, prompt: np.ndarray, first_tok: int,
              total_tokens: int) -> None:
        prompt = np.asarray(prompt, np.int32)  # sync-ok: host prompt
        S = len(prompt)
        pages = self.cache.admit(slot, total_tokens)
        assert pages is not None, \
            "drafter pool exhausted — size it fully provisioned"
        # bucketed prompt prefill, the engine admission's page-padding
        # contract (shared helper — the two paths must not drift)
        from deepspeed_tpu.serving.paged_cache import \
            padded_prefill_inputs
        import jax.numpy as jnp
        P = self.adapter.spec.page_size
        ids, page_vec = padded_prefill_inputs(
            prompt, pages, P, self.adapter.max_prompt_len() // P)
        pool, _ = self.adapter.prefill(
            self.cache.pool, jnp.asarray(ids), jnp.asarray(S, jnp.int32),
            jnp.asarray(page_vec))
        self.cache.pool = pool
        # the target's first (prefill-sampled) token is the drafter's
        # next feed — its own prefill prediction is discarded so the
        # two caches stay aligned on the committed stream
        self.pos[slot] = S
        self.last[slot] = first_tok

    def release(self, slot: int) -> None:
        self.cache.release(slot)
        self.pos[slot] = -1
        self.last[slot] = 0

    def commit(self, slot: int, committed: List[int], new_pos: int,
               last_tok: int) -> None:
        """Rollback/fast-forward to the verifier's outcome: rows for the
        accepted span were fed the same tokens on both models, so the
        drafter just adopts the target's committed position (stale draft
        rows beyond it are overwritten by the next round's appends)."""
        self.pos[slot] = new_pos
        self.last[slot] = last_tok

    def observe_plain(self, slots: List[int], feed: np.ndarray,
                      committed: np.ndarray) -> None:
        """The engine committed tokens in a PLAIN tick the drafter never
        drafted for: teacher-force the fed tokens through the drafter's
        own cache (one ``verify`` append dispatch — its greedy output is
        discarded, only the K/V rows matter) so ``pos`` can fast-forward
        over rows that actually exist. Skipping this would leave the
        drafter's cache holding NO rows at the committed positions and
        every later draft round attending garbage."""
        B = len(self.pos)
        V = feed.shape[0]
        toks = np.zeros((B, V), np.int32)
        pos = np.full((B,), -1, np.int32)
        for s in slots:
            toks[s] = feed[:, s]
            pos[s] = self.pos[s]
        pool, _, _ = self.adapter.verify(self.cache.pool, toks, pos,
                                         self.cache.page_table)
        self.cache.pool = pool
        for s in slots:
            self.pos[s] += V
            self.last[s] = int(committed[-1, s])

    def draft(self, active_slots: List[int], k: int) -> np.ndarray:
        """k greedy draft tokens per active slot via one k-step tick
        over the drafter's own paged cache."""
        import jax.numpy as jnp
        toks = np.asarray(self.last, np.int32)  # sync-ok: host ints
        pos = np.asarray(self.pos, np.int32)    # sync-ok: host ints
        B = len(self.pos)
        # greedy drafting: the per-slot sampling seeds are never used
        # (temps stay 0), zeros keep the compiled tick signature shared
        # with the target engine's
        pool, toks_seq, _ = self.adapter.tick(
            self.cache.pool, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(self.cache.page_table),
            jnp.zeros((B,), jnp.uint32), jnp.zeros((B,), jnp.int32),
            jnp.asarray(self._temps), steps=k)
        self.cache.pool = pool
        toks_seq = np.asarray(toks_seq)   # sync-ok: drafts feed the
        #                                   host accept/reject loop
        for s in active_slots:
            self.pos[s] += k              # provisional; commit() rewinds
            self.last[s] = toks_seq[-1, s]
        return toks_seq[:, active_slots].T.astype(np.int32)

    def restore_slot(self, slot: int, prompt: np.ndarray,
                     generated: List[int], total_tokens: int) -> None:
        """Elastic-restore realignment (see NGramDrafter.restore_slot):
        re-prefill the prompt through the drafter's own cache, then
        teacher-force the committed tokens so its K/V holds real rows
        at every committed position."""
        _realign_restored(self, slot, prompt, generated, total_tokens)
