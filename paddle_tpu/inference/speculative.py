"""Speculative decoding inside the one donated decode step (ISSUE 13).

Every decode iteration of :class:`~.serving.PagedServingEngine` emits
exactly one token per sequence — token latency is one full target
forward per token.  This module recovers >1 token per target forward
(Leviathan et al., "Fast Inference from Transformers via Speculative
Decoding"; Saxena, "Prompt Lookup Decoding") while keeping every
serving invariant earned in PRs 5/8/9:

* **one donated decode executable, forever** — each iteration drafts
  ``spec_k`` candidate tokens per active row, then ONE jitted,
  buffer-donated VERIFY step scores all ``k+1`` window positions in a
  single batched target forward, computes the longest accepted prefix
  IN-GRAPH (accept length is a traced value — there is no compile per
  accept length), and commits it with a masked page-aligned scatter.
  ``decode_compiles`` stays 1; draft mode adds exactly two more
  executables (``spec_draft_compiles``: the draft prefill chunk and the
  fused catch-up+draft-k step), a fixed set warmup covers.
* **rejected tokens never corrupt paged KV** — the verify forward is
  deferred-commit (models/gpt.py::decode_step_paged_verify): the page
  pool is read-only during the forward, and the commit scatter
  redirects every rejected window lane to the scratch page.  Accepted
  positions land the exact bytes (and, on the int8 pool, the exact
  once-per-position scales) a sequential decode would have written, so
  the prefix-hash/page-byte determinism contract survives — the
  ``spec_reject`` fault's regression test proves an all-reject verify
  leaves the pool byte-identical to a never-speculated run.
* **token-exact greedy output** — accepted drafts equal the verify's
  own argmax by construction, and the bonus token IS that argmax, so
  the committed stream is exactly what the non-speculative engine
  would emit, through churn, chunked prefill, preemption-retry, and
  ``kv_dtype="int8"``.

Two drafting modes:

* ``"ngram"`` — model-free prompt-lookup: draft the continuation of
  the most recent earlier occurrence of the row's trailing n-gram in
  its OWN token history (the host mirror of its paged KV contents:
  prompt + committed tokens).  Pure numpy over host-resident ints — it
  adds ZERO device syncs and zero executables.  On the repetitive /
  shared-prefix traffic a production fleet actually sees (and on
  greedy decoding's attractor cycles) this alone sustains multiple
  accepted tokens per verify.
* ``"draft"`` — a small seeded draft GPT (its own slot-contiguous KV
  cache, ``2*spec_k`` positions deeper than the target's ``max_len``)
  proposes the k candidates; each iteration one fused executable
  catches the draft cache up on last step's committed tokens and
  self-samples the next k (models/gpt.py::draft_catchup_and_draft).
  Draft K/V past the committed length are speculative garbage masked
  by the fill bound, overwritten by the next catch-up — the draft
  cache needs no rollback machinery.

Knobs (constructor args, with ``PADDLE_SPEC_*`` env fallbacks):
``spec_mode`` (env ``PADDLE_SPEC_MODE``, default "ngram"), ``spec_k``
(``PADDLE_SPEC_K``, default 4), ``spec_ngram_max``
(``PADDLE_SPEC_NGRAM``, default 3), ``spec_draft_cfg`` /
``spec_draft_seed`` (``PADDLE_SPEC_DRAFT_SEED``, default 0).

Telemetry rides the ``serving.*`` family: ``drafted_tokens`` /
``accepted_tokens`` / ``rejected_tokens`` / ``spec_steps`` counters,
the ``serving.accepted_tokens_per_step`` gauge (committed tokens per
row-verify, the >1 speedup factor bench.py asserts), and
``serving_step`` JSONL events carry ``drafted``/``accepted``/
``committed`` fields.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from ..framework import compile_cache as _cc
from ..models import gpt
from ..observability import metrics, timeline, tracing
from ..testing import faults as _faults
from .serving import PagedServingEngine, _donation_enabled

__all__ = ["SpeculativeServingEngine", "ngram_draft", "accept_commit",
           "SPEC_MODES"]

SPEC_MODES = ("draft", "ngram")


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


# --------------------------------------------------------------------------
# model-free drafting: prompt lookup / n-gram continuation
# --------------------------------------------------------------------------

def ngram_draft(history, k, max_ngram=3):
    """Prompt-lookup drafting (Saxena): find the most recent EARLIER
    occurrence of the trailing ``n``-gram of ``history`` (trying
    ``max_ngram`` down to 1) and draft the ``k`` tokens that followed
    it; pad with the last drafted (or last history) token when the
    match sits near the end.  No match at any n: draft the last token
    repeated (a cheap guess — wrong drafts cost nothing but their lane
    of an already-paid verify).

    Pure numpy over the HOST-side token mirror (prompt + committed
    tokens) — the matcher never touches device values, so it introduces
    no host-sync into the decode loop."""
    h = np.asarray(history, np.int64).reshape(-1)
    k = int(k)
    if h.size == 0:
        return np.zeros((k,), np.int32)
    for n in range(min(int(max_ngram), h.size - 1), 0, -1):
        pat = h[-n:]
        # candidate windows live in h[:-1]: every length-n window whose
        # continuation exists and which is not the trailing n-gram
        # itself (sliding over h[:-1] excludes it by construction)
        win = np.lib.stride_tricks.sliding_window_view(h[:-1], n)
        hits = np.nonzero((win == pat[None, :]).all(axis=1))[0]
        if hits.size:
            s = int(hits[-1])
            cont = h[s + n:s + n + k]
            out = np.empty((k,), np.int64)
            out[:cont.size] = cont
            out[cont.size:] = cont[-1]
            return out.astype(np.int32)
    return np.full((k,), int(h[-1]), np.int32)


# --------------------------------------------------------------------------
# accept / commit math (traced; unit-tested directly)
# --------------------------------------------------------------------------

def accept_commit(drafts, greedy, caps, eos_ids, force_reject):
    """The longest-accepted-prefix commit math, all traced values so it
    lives INSIDE the one donated verify executable.

    ``drafts`` int32 [S, k] (the candidates, window positions 1..k);
    ``greedy`` int32 [S, k+1] (the verify's argmax at every window
    position); ``caps`` int32 [S] (commit budget: remaining
    ``max_new_tokens``, clipped to k+1; 0 silences an inactive row);
    ``eos_ids`` int32 [S] (-1 = no eos); ``force_reject`` int32 scalar
    (the ``spec_reject`` fault: accept length forced to 0).

    Returns ``(out_toks [S, k+1], n_commit [S])``: the committed stream
    is ``out_toks[s, :n_commit[s]]``.  Accepted drafts equal the greedy
    row by definition, and the bonus token is ``greedy[accept_len]``,
    so ``out_toks`` IS the greedy row — token-exactness with the
    non-speculative engine is by construction, not by comparison.
    ``n_commit`` truncates at the commit budget and at the first eos
    (the eos commits, nothing after it — and critically nothing after
    it is K/V-committed either)."""
    import jax.numpy as jnp
    S, W = greedy.shape
    k = W - 1
    if k:
        eq = (drafts == greedy[:, :k]).astype(jnp.int32)
        accept_len = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)
    else:
        accept_len = jnp.zeros((S,), jnp.int32)
    accept_len = jnp.where(force_reject > 0,
                           jnp.zeros_like(accept_len), accept_len)
    pos = jnp.arange(W)[None, :]
    n0 = jnp.minimum(accept_len + 1, caps)
    hit = (greedy == eos_ids[:, None]) & (pos < n0[:, None])
    first = jnp.min(jnp.where(hit, pos, W), axis=1)
    n_commit = jnp.where(first < W, first + 1, n0).astype(jnp.int32)
    return greedy.astype(jnp.int32), n_commit


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class SpeculativeServingEngine(PagedServingEngine):
    """:class:`~.serving.PagedServingEngine` whose decode step drafts
    ``spec_k`` candidates per row and verifies all ``k+1`` positions in
    ONE donated executable (module docstring has the full contract).
    Greedy output is token-exact with the non-speculative paged engine;
    only the number of target forwards per token changes."""

    def __init__(self, model, *, spec_mode=None, spec_k=None,
                 spec_draft_cfg=None, spec_draft_seed=None,
                 spec_ngram_max=None, spec_draft_chunk=16, **kw):
        mode = spec_mode or os.environ.get("PADDLE_SPEC_MODE", "ngram")
        if mode not in SPEC_MODES:
            raise ValueError(
                f"spec_mode must be one of {SPEC_MODES}, got {mode!r} "
                "(spec_mode=off means: use PagedServingEngine)")
        k = int(spec_k if spec_k is not None
                else _env_int("PADDLE_SPEC_K", 4))
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        # set before super().__init__: _rebuild_cache (called there)
        # allocates the draft cache from these
        self._spec_mode_val = mode
        self._spec_k_val = k
        self._ngram_max = int(
            spec_ngram_max if spec_ngram_max is not None
            else _env_int("PADDLE_SPEC_NGRAM", 3))
        self._draft_seed = int(
            spec_draft_seed if spec_draft_seed is not None
            else _env_int("PADDLE_SPEC_DRAFT_SEED", 0))
        self._spec_draft_cfg_arg = spec_draft_cfg
        self._draft_chunk = int(spec_draft_chunk)
        self._draft_cfg = None
        self._draft_params = None
        self._draft_k = self._draft_v = None
        self._draft_jit = None
        self._draft_prefill_jit = None
        self._draft_site = _cc.site("serving.draft", maxsize=4)
        self._commit_sum = 0            # committed tokens over live traffic
        self._rowstep_sum = 0           # active rows x verify steps
        super().__init__(model, **kw)
        if self._pp > 1:
            raise ValueError(
                "pp > 1 does not compose with speculative decoding yet "
                "— the verify executable is the GSPMD paged step, not "
                "the 1F1B stage loop (use PagedServingEngine(pp=...))")
        self.spec_mode = mode           # the contract attestation fields
        self.spec_k = k
        self._g_accept = metrics.gauge("serving.accepted_tokens_per_step")

    def _aot_sig(self):
        dc = (",".join(f"{k}={v}" for k, v in sorted(
            dataclasses.asdict(self._draft_cfg).items()))
            if self._draft_cfg is not None else None)
        return (f"{super()._aot_sig()}/spec={self._spec_mode_val}"
                f"/k={self._spec_k_val}/dchunk={self._draft_chunk}"
                f"/dcfg[{dc}]")

    def _aot_has_core(self):
        """The spec engine's decode site holds the VERIFY executable
        (the single-token paged decode never runs here); draft mode
        additionally needs both draft executables before a warmup wave
        may be skipped — a skipped wave with a missing draft artifact
        would push the draft compile into live traffic."""
        topo = self._topology()
        core = _cc.artifact_ready(self._aot_key("verify"), topology=topo)
        if core and self._spec_mode_val == "draft":
            core = (_cc.artifact_ready(
                self._aot_key("draft_prefill", c=self._draft_chunk),
                topology=topo)
                and _cc.artifact_ready(self._aot_key("draft_step"),
                                       topology=topo))
        return core

    # ------------------------------------------------------- draft model
    def _build_draft_cfg(self):
        """The draft GPT config: user-supplied kwargs (or a GPTConfig)
        with ``max_seq_len`` raised to the draft cache's need, or a
        derived half-size default.  The draft's vocab must match the
        target's — its candidates feed the target verify directly."""
        need = self.max_len + 2 * self._spec_k_val
        base = self._spec_draft_cfg_arg
        if base is None:
            c = self.cfg
            heads = max(1, c.num_heads // 2)
            hidden = max(heads, (c.hidden_size // 2 // heads) * heads)
            kwargs = dict(vocab_size=c.vocab_size, hidden_size=hidden,
                          num_layers=max(1, c.num_layers // 2),
                          num_heads=heads, dtype=c.dtype,
                          ffn_size=0)
        elif isinstance(base, gpt.GPTConfig):
            kwargs = dataclasses.asdict(base)
        else:
            kwargs = dict(base)
        kwargs["max_seq_len"] = max(int(kwargs.get("max_seq_len") or 0),
                                    need)
        # the draft decodes through the slot cache's lax math only
        kwargs["use_flash"] = False
        kwargs["remat"] = False
        cfg = gpt.GPTConfig(**kwargs)
        if cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size {cfg.vocab_size} != target "
                f"{self.cfg.vocab_size} — draft candidates feed the "
                "target verify")
        return cfg

    def _rebuild_cache(self):
        super()._rebuild_cache()
        if self._spec_mode_val == "draft":
            if self._draft_cfg is None:
                import jax
                self._draft_cfg = self._build_draft_cfg()
                self._draft_params = gpt.init_params(
                    self._draft_cfg, jax.random.PRNGKey(self._draft_seed))
                if self._mesh is not None:
                    # a tp-sharded target rejects operands committed
                    # off-mesh: the draft model is tiny, so it rides
                    # REPLICATED on the same mesh (its derived head
                    # count need not divide tp)
                    self._draft_params = gpt.replicate_on_mesh(
                        self._draft_params, self._mesh)
            # 2k positions deeper than the target cache: the fused
            # catch-up+draft step writes up to lens + 2k - 1
            dmax = self.max_len + 2 * self._spec_k_val
            cache = gpt.init_slot_cache(self._draft_cfg, self.slots, dmax)
            if self._mesh is not None:
                cache = gpt.replicate_on_mesh(
                    {"k": cache["k"], "v": cache["v"]}, self._mesh)
            self._draft_k, self._draft_v = cache["k"], cache["v"]
        self._draft_lens = np.zeros((self.slots,), np.int32)

    def _build_draft_prefill(self, C):
        jax = self._jax
        dcfg = self._draft_cfg

        def dprefill(params, cache_k, cache_v, toks, slot, offset):
            assert toks.shape == (1, C), (toks.shape, C)  # one chunk exe
            return gpt.draft_prefill_slot(params, toks, dcfg, cache_k,
                                          cache_v, slot, offset)

        donate = (1, 2) if _donation_enabled() else ()
        return jax.jit(dprefill, donate_argnums=donate)

    def _build_draft_step(self):
        jax = self._jax
        dcfg = self._draft_cfg
        k = self._spec_k_val

        def dstep(params, cache_k, cache_v, ctx, n_ctx, lens):
            return gpt.draft_catchup_and_draft(params, dcfg, cache_k,
                                               cache_v, ctx, n_ctx,
                                               lens, k)

        donate = (1, 2) if _donation_enabled() else ()
        return jax.jit(dstep, donate_argnums=donate)

    def _draft_ingest(self, req):
        """Prefill the draft model's cache with the row's prompt (fixed
        C-token chunks through ONE executable) and arm the pending-draft
        backlog with the tokens the target has already committed — at
        activation that is exactly the prefill's first sampled token (a
        preemption retry restarts from the prompt, so it can never be
        mid-history)."""
        jnp = self._jnp
        s = req.slot
        C = self._draft_chunk
        p = np.asarray(req.prompt, np.int32)
        for pos in range(0, len(p), C):
            take = min(C, len(p) - pos)
            toks = np.zeros((1, C), np.int32)
            toks[0, :take] = p[pos:pos + take]
            operands = (self._draft_params, self._draft_k, self._draft_v,
                        jnp.asarray(toks), np.int32(s), np.int32(pos))
            if self._draft_prefill_jit is None:
                donate = (1, 2) if _donation_enabled() else ()
                self._draft_prefill_jit = self._draft_site.get(
                    _cc.make_key("draft_prefill", C, donate=donate,
                                 mesh=self._mesh_key()),
                    lambda: self._build_draft_prefill(C),
                    stable_key=self._aot_key("draft_prefill", c=C),
                    example_args=operands, topology=self._topology())
                self._inc("spec_draft_compiles")
            self._draft_k, self._draft_v = self._draft_prefill_jit(
                *operands)
        self._draft_lens[s] = len(p)
        req.pending_draft = list(req.tokens)

    def _spec_draft_sync(self):
        """Ingest newly-activated rows into the draft cache (draft mode
        only).  ``pending_draft is None`` marks a row the draft has
        never seen this admission — covers wave admissions, chunked
        admissions, and preemption retries uniformly (reset_for_retry
        scrubs it back to None)."""
        if self._spec_mode_val != "draft":
            return
        for s in range(self.slots):
            if not self._active[s]:
                continue
            req = self._slot_req[s]
            if req.pending_draft is None:
                self._draft_ingest(req)

    # ---------------------------------------------------------- drafting
    def _make_drafts(self):
        """The verify window's token matrix [S, k+1] (position 0: the
        last committed token; 1..k: draft candidates) as a device array.
        Draft mode keeps the candidates ON DEVICE (no readback — the
        only host sync of the loop stays the committed-token one)."""
        jnp = self._jnp
        k = self._spec_k_val
        if self._spec_mode_val == "ngram":
            toks = np.zeros((self.slots, k + 1), np.int32)
            for s in range(self.slots):
                if not self._active[s]:
                    continue
                req = self._slot_req[s]
                toks[s, 0] = self._last_tok[s]
                hist = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                toks[s, 1:] = ngram_draft(hist, k, self._ngram_max)
            return jnp.asarray(toks)
        # draft mode: catch the draft cache up on last step's committed
        # tokens, then self-sample k candidates — one fused executable
        ctx = np.zeros((self.slots, k + 1), np.int32)
        n_ctx = np.zeros((self.slots,), np.int32)
        for s in range(self.slots):
            if not self._active[s]:
                continue
            pend = self._slot_req[s].pending_draft or []
            ctx[s, :len(pend)] = pend
            n_ctx[s] = len(pend)
        operands = (self._draft_params, self._draft_k, self._draft_v,
                    jnp.asarray(ctx), jnp.asarray(n_ctx),
                    jnp.asarray(self._draft_lens))
        if self._draft_jit is None:
            donate = (1, 2) if _donation_enabled() else ()
            self._draft_jit = self._draft_site.get(
                _cc.make_key("draft_step", k, donate=donate,
                             mesh=self._mesh_key()),
                self._build_draft_step,
                stable_key=self._aot_key("draft_step"),
                example_args=operands, topology=self._topology())
            self._inc("spec_draft_compiles")
        with timeline.span("serving.spec_draft",
                           active=int(self._active.sum())):
            self._draft_k, self._draft_v, drafts = self._draft_jit(
                *operands)
        for s in range(self.slots):
            if self._active[s]:
                self._draft_lens[s] += int(n_ctx[s])
                self._slot_req[s].pending_draft = []
        last = jnp.asarray(self._last_tok)[:, None]
        return jnp.concatenate([last, drafts], axis=1)

    # ------------------------------------------------------------ paging
    def _ensure_spec_pages(self, caps):
        """Writable page coordinates for each row's commit window:
        positions ``lens[s] .. lens[s] + caps[s] - 1`` (the budget-
        capped part — positions past the cap can never commit, their
        lanes redirect to scratch in-graph).  Same preempt-the-newest
        retry loop as the base engine's single-token version."""
        ps = self._page_size
        W = self._spec_k_val + 1
        wpages = np.zeros((self.slots, W), np.int32)
        woffs = np.zeros((self.slots, W), np.int32)
        while True:
            try:
                for s in range(self.slots):
                    wpages[s] = 0
                    woffs[s] = 0
                    if not self._active[s]:
                        continue
                    pos = int(self._lens[s])
                    n = int(caps[s])
                    pids, offs, cows = self._pager.ensure_append_window(
                        s, pos, n)
                    for src, dst in cows:
                        self._copy_page(src, dst)
                    for d, pid in enumerate(pids):
                        self._tables_np[s, (pos + d) // ps] = pid
                    wpages[s, :n] = pids
                    woffs[s, :n] = offs
                return wpages, woffs
            except self._PagesExhausted as e:
                victim = self._newest_victim()
                if victim is None:
                    raise
                self._preempt(victim, str(e))

    # ------------------------------------------------------------ verify
    def _build_verify(self):
        jax, jnp = self._jax, self._jnp
        cfg = self.cfg
        cap = self.capture_logits
        n = self._n_cache

        def verify(params, *args):
            cache = args[:n]
            (toks, ptab, wpages, woffs, lens, caps, eos_ids,
             force) = args[n:]
            logits, windows = gpt.decode_step_paged_verify(
                params, cfg, cache, toks, ptab, lens)
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)  # [S, W]
            out_toks, n_commit = accept_commit(toks[:, 1:], greedy, caps,
                                               eos_ids, force)
            # masked page-aligned commit: window lane j holds the K/V of
            # the token CONSUMED at position lens+j, valid for exactly
            # j < n_commit; every rejected/padded lane redirects to the
            # scratch page, so the pool's real pages only ever receive
            # the bytes a sequential decode would have written
            mask = jnp.arange(toks.shape[1])[None, :] < n_commit[:, None]
            wp = jnp.where(mask, wpages, 0)
            wo = jnp.where(mask, woffs, 0)
            out_cache = self._constrain_cache(tuple(
                c.at[:, wp, wo].set(w) for c, w in zip(cache, windows)))
            if cap:
                return (*out_cache, out_toks, n_commit, logits)
            return (*out_cache, out_toks, n_commit)

        donate = (tuple(range(1, 1 + n)) if _donation_enabled() else ())
        return jax.jit(verify, donate_argnums=donate)

    # ------------------------------------------------------------ driving
    def _step_inner(self):
        with timeline.span("serving.admit"):
            self._admit()
        # the verify window is built on the host from every slot's last
        # committed token: the waves just dispatched are committed first
        self._drain("speculative")
        self._advance_chunks()
        if not self._active.any():
            return
        jnp = self._jnp
        k = self._spec_k_val
        W = k + 1
        force_reject = 0
        if _faults.active() and not self._warming:
            if _faults.page_exhaustion_check(
                    step=self._counts["decode_steps"] + 1):
                victim = self._newest_victim()
                if victim is not None:
                    self._preempt(victim, "injected page_exhaustion")
            _faults.engine_step_error(self._counts["decode_steps"] + 1)
            _faults.replica_kill_check(
                step=self._counts["decode_steps"] + 1)
            if _faults.spec_reject_check(
                    step=self._counts["decode_steps"] + 1):
                force_reject = 1
        if not self._active.any():
            return                  # the injected preemption emptied it
        caps = np.zeros((self.slots,), np.int32)
        eos_ids = np.full((self.slots,), -1, np.int32)
        for s in range(self.slots):
            if not self._active[s]:
                continue
            req = self._slot_req[s]
            caps[s] = min(W, req.max_new_tokens - len(req.tokens))
            if req.eos_token is not None:
                eos_ids[s] = int(req.eos_token)
        wpages, woffs = self._ensure_spec_pages(caps)
        if not self._active.any():
            return
        # a mid-ensure preemption freed a slot after its cap was set:
        # silence it so the in-graph commit math treats it as inactive
        caps = np.where(self._active, caps, 0).astype(np.int32)
        self._spec_draft_sync()
        toks_dev = self._make_drafts()
        operands = (self.params, *self._cache_operands(), toks_dev,
                    jnp.asarray(self._tables_np), jnp.asarray(wpages),
                    jnp.asarray(woffs), jnp.asarray(self._lens),
                    jnp.asarray(caps), jnp.asarray(eos_ids),
                    np.int32(force_reject))
        if self._decode_jit is None:
            donate = self._donate()
            self._decode_jit = self._decode_site.get(
                _cc.make_key("verify", donate=donate,
                             mesh=self._mesh_key()),
                self._build_verify,
                stable_key=self._aot_key("verify"),
                example_args=operands, topology=self._topology())
            self._inc("decode_compiles")
        finished = []
        # the decode span IS the serving.decode_step_s interval: from the
        # jitted call to the end of the commit loop
        with timeline.span("serving.decode",
                           active=int(self._active.sum()), paged=True,
                           spec=self._spec_mode_val) as decode:
            out = self._decode_jit(*operands)
            self._set_cache(out[:self._n_cache])
            # ptl: disable-next=PTL004 -- capture_logits debug mode readback
            logits_np = (np.asarray(out[self._n_cache + 2])
                         if self.capture_logits else None)
            self._inc("decode_steps")
            self._count_quant_matmuls()
            # committed-token readback: THE designed device->host sync of
            # the speculative decode loop (same role as the non-spec
            # engine's sampled-token fetch, amortized over the whole window)
            # ptl: disable-next=PTL004 -- committed-token readback
            out_np = np.asarray(out[self._n_cache])
            # ptl: disable-next=PTL004 -- committed-count readback
            ncom_np = np.asarray(out[self._n_cache + 1])
            committed, rows = 0, 0
            for s in range(self.slots):
                if not self._active[s]:
                    continue
                req = self._slot_req[s]
                nc = int(ncom_np[s])
                rows += 1
                toks_row = [int(t) for t in out_np[s, :nc]]
                self._lens[s] += nc
                self._append_tokens(req, toks_row,
                                    logits_np[s] if logits_np is not None
                                    else None)
                self._last_tok[s] = toks_row[-1]
                committed += nc
                self._inc("drafted_tokens", k)
                self._inc("accepted_tokens", nc - 1)
                self._inc("rejected_tokens", k - (nc - 1))
                if self._spec_mode_val == "draft" and not req.done:
                    req.pending_draft = toks_row
                if req.done:
                    finished.append(req)
            self._inc("spec_steps")
            if not self._warming:
                self._commit_sum += committed
                self._rowstep_sum += rows
                if self._rowstep_sum:
                    self._g_accept.set(round(
                        self._commit_sum / self._rowstep_sum, 4))
        dt = decode.dur
        if not self._warming:
            self._h_decode.observe(dt)
        self._g_occ.set(int(self._active.sum()))
        if not self._warming and timeline.telemetry_dir():
            timeline.emit({"event": "serving_step",
                           "active": int(self._active.sum()),
                           "queue": len(self._queue),
                           "decode_s": round(dt, 6),
                           "finished": len(finished),
                           "pages_in_use": self._pager.pages_in_use(),
                           "finished_ids": [str(r.id) for r in finished],
                           # per-process total order + emitter (ISSUE 19)
                           "seq": tracing.seq(),
                           "engine": self._engine_id,
                           "replica": self._replica,
                           "spec_mode": self._spec_mode_val,
                           "drafted": k * rows,
                           "accepted": committed - rows,
                           "committed": committed,
                           "accepted_tokens_per_step": round(
                               committed / max(1, rows), 4)})
        if tracing.enabled() and not self._warming:
            for r in finished:
                tracing.event("decode_iter", trace_id=r.trace_id,
                              request_id=r.id, iters=len(r.tokens),
                              decode_s=round(dt, 6), drafted=k * rows,
                              accepted=committed - rows,
                              accepted_tokens_per_step=round(
                                  committed / max(1, rows), 4),
                              engine=self._engine_id)

    # --------------------------------------------------------------- views
    def accepted_tokens_per_step(self):
        """Committed tokens per (row, verify) over live traffic — the
        speedup factor vs one-token decode (1.0 means speculation never
        helped; the bench demands > 1.5 on repetitive traffic)."""
        if not self._rowstep_sum:
            return 0.0
        return round(self._commit_sum / self._rowstep_sum, 4)

    def stats(self):
        out = super().stats()
        out["spec_k"] = self.spec_k
        out["accepted_tokens_per_step"] = self.accepted_tokens_per_step()
        return out
