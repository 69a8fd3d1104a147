"""High-throughput serving engine: continuous batching over a slot-pooled
KV cache (ISSUE 5 tentpole).

The reference serves frozen programs through a request-at-a-time predictor
(ref: paddle/fluid/inference/api/analysis_predictor.cc) — fine for CNNs,
hopeless for autoregressive decoding, where request-level batching wastes
most of the batch on padding and parks finished sequences until the
slowest one drains.  This engine is the Orca/vLLM-shaped redesign:

* **slots, not batches** — a fixed pool of ``slots`` decode lanes backed
  by ONE shared ``[L, slots, max_len, nh, hd]`` KV buffer with a per-slot
  fill length (models/gpt.py::init_slot_cache).  Every iteration one
  jitted, **buffer-donated** decode step (models/gpt.py::decode_step_slots)
  advances all in-flight sequences a token; a finished sequence's slot is
  handed to the next queued request immediately — no drain barrier, no
  padding rows beyond the pool size.  The decode executable's signature
  never changes, so requests churning through slots cost ZERO retraces.
* **bucketed prefill** — prompts are padded to a ``(batch, seq)`` shape
  ladder and prefilled through per-bucket executables (cached in a
  :class:`~paddle_tpu.ops.dispatch.SignatureLRU`, the dispatch cache's
  keying discipline), so compile count is bounded by the ladder size no
  matter how many distinct prompt lengths arrive.  Each prefill executable
  also scatters its K/V rows straight into the donated slot buffer and
  returns the first sampled token — one XLA program per admission wave.
* **persistent compiles** — ``PADDLE_JIT_CACHE_DIR`` (via
  framework/jax_compat.py::enable_persistent_cache) makes a server restart
  reload yesterday's executables instead of re-running XLA.

Telemetry rides the PR-4 registry under ``serving.*``: queue depth and
slot occupancy gauges, prefill/decode/request latency histograms,
tokens/s, and compile counters the bench asserts on.

:class:`PagedServingEngine` (ISSUE 8, bottom of this module) replaces
the slot-contiguous pool with a block-table paged KV cache — fixed-size
pages, per-slot page tables, shared-prefix page reuse, chunked prefill —
while keeping every invariant above (one donated decode executable,
token-exact greedy parity, bounded prefill compiles).
"""
from __future__ import annotations

import collections
import hashlib
import itertools
import os
import time
import uuid

import numpy as np

from ..framework import compile_cache as _cc
from ..framework import jax_compat
from ..models import gpt
from ..observability import metrics, timeline, tracing
from ..testing import faults as _faults

DEFAULT_BATCH_BUCKETS = (1, 2, 4)

# per-process engine instance ids: serving_step / request_complete
# events stamp "engine" so multi-engine processes (tests, spec decode's
# draft+target pair) stay distinguishable in one rank's JSONL
_ENGINE_IDS = itertools.count()


def pool_relayouts(hlo_text, pools):
    """What a compiled serving program (``compiled.as_text()``) does to
    the paged KV pool beyond reading and writing it in place, as a list
    of findings: a page pool that does not enter major-to-minor, and
    every instruction named ``copy`` / ``transpose`` (fusions of them
    included) over a shape that holds the pool's page count and at
    least one LAYER of pages' elements.  ``pools``: the program's pool
    operands (arrays or shapes, the engine's ``_cache_operands()``).
    An int8 pool's scale rows ([.., nh] minor, which the device stores
    page-minor: PERF.md section 7) are still relaid out, a layer's slice
    in decode and the array in a prefill wave — 4 / hd of the pages'
    bytes, under the bar.  Read by tests/test_chip_compile.py (compiled
    for a described chip) and by chip_smoke.py (on the chip)."""
    import math
    import re
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->",
                      hlo_text).group(1)
    pages = [p for p in pools if p.ndim == 4 and p.shape[-1] % 128 == 0]
    found = []
    for p in pages:
        dims = ",".join(map(str, p.shape))
        if f"[{dims}]{{3,2,1,0:" not in entry:
            found.append(f"pool [{dims}] does not enter as {{3,2,1,0}}")
    num_pages = pools[0].shape[1]
    layer_elems = math.prod(pools[0].shape[1:])
    for line in hlo_text.splitlines():
        m = re.search(r"^\s*(?:ROOT )?%\S*(?:copy|transpose)\S* = "
                      r"\w+\[([\d,]*)\]", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if num_pages in dims and math.prod(dims) >= layer_elems:
            found.append(line.strip()[:160])
    return found


# What a model family gives the paged engine (models/gpt.py,
# deepseek_v3.py, ouro.py and phi4flash.py all do): everything else in
# this file is the same scheduler, pager, spans and counters for every
# family.
FAMILY_INTERFACE = (
    "check_serving",            # raise, by name, for unbuilt compositions
    "shard_params_for_serving", "kv_pool_spec",
    "init_paged_pools",         # the donated pool arrays, operand order
    "prefill_paged", "chunk_paged", "decode_paged",
    "kv_bytes_per_position", "prefix_salt")
# (a family whose ``decode_paged`` returns counts beside the logits also
# gives ``decode_extra_stats(cfg, flat) -> {counter: increment}``; one
# whose decode kernel's grid is (slot, page group) by a rule of shapes
# gives ``decode_group_pages(cfg, pools, table_width, tp) -> G``; one
# that keeps state per SLOT beside its pages — a recurrent layer's
# state, a window's ring — gives ``slot_state_arrays(cfg) -> n``: the
# last n arrays of ``init_paged_pools``' tuple are indexed by slot, not
# by page.  Such a family is handed what it needs to keep them:
# ``init_paged_pools(..., slots=)``, the rows' slot ids in the prefill
# program, ``prefill_paged(..., slots=)`` (a pad row: an id past the
# slots), which must overwrite the state of those slots — that is the
# reset of a slot given to a new request — and the slot and the true
# length of a chunk, ``chunk_paged(..., slot=, take=)``, which returns
# the last true row's logits [V] alone; its decode leaves the state of a
# slot with ``lens == 0`` as it is.  Page copies move the arrays before
# those n only, ``stats()`` reports the n arrays' bytes as
# ``slot_state_bytes``, and ``slot_state(slot)`` hands a reference check
# the family's ``slot_state_of(cfg, pools, slot)``.  A family whose
# ``prefill_paged`` returns counts behind the pools gives
# ``prefill_extra_stats(cfg, flat) -> {counter: increment}``: they ride
# the first tokens' readback, as decode's do)


def family_of(cfg):
    """The model family that serves ``cfg``: the module that defines
    its config class.  Selected by the type of ``cfg`` alone — no
    engine argument names a family."""
    import sys
    mod = sys.modules[type(cfg).__module__]
    missing = [n for n in FAMILY_INTERFACE if not hasattr(mod, n)]
    if missing:
        raise TypeError(
            f"{type(cfg).__name__} ({mod.__name__}) is not a served model "
            f"family: the module lacks {missing}")
    return mod


def _cfg_of(model):
    return model[1] if (isinstance(model, (tuple, list))
                        and len(model) == 2) else model.cfg


class ServingQueueFull(RuntimeError):
    """submit() back-pressure: the bounded admission queue is at
    ``max_queue`` — callers must retry/shed, exactly like a 429."""


def _donation_enabled():
    """Donate the slot KV buffers into prefill/decode executables
    (in-place update, no second cache-sized allocation).  Same contract
    as the fused optimizer step: ``PADDLE_TPU_SERVING_DONATE`` 0/1
    forces, auto skips CPU (whose donation path only warns)."""
    return jax_compat.donation_enabled("PADDLE_TPU_SERVING_DONATE")


# the shape-ladder maths live in the unified compile layer now
_pow2_ladder = _cc.pow2_ladder


def serving_stats():
    """The ``serving.*`` counter family with its default keys
    materialized.  Monitoring processes should read
    ``paddle_tpu.inference.serving_stats`` / ``profiler.serving_stats``
    instead — same registry cells, no serving-stack import."""
    return dict(_stats_family())


def _stats_family():
    return metrics.stats_family("serving", {
        "prefill_compiles": 0, "decode_compiles": 0,
        "prefill_calls": 0, "decode_steps": 0,
        "requests_admitted": 0, "requests_completed": 0,
        "tokens_generated": 0, "queue_rejects": 0,
        "step_aborts": 0, "requests_aborted": 0,
        "requests_cancelled": 0,
        "standalone_compiles": 0,
        # paged-KV family (PagedServingEngine; zero on slot engines)
        "prefill_chunks": 0, "prefix_page_hits": 0,
        "prefix_page_misses": 0, "cow_copies": 0, "preemptions": 0,
        # what the committed prefill waves were given (prompt tokens)
        # and what their programs ran (batch x seq rows, padding
        # included): the sums of stats()["prefill_by_bucket"]
        "prefill_tokens": 0, "prefill_padded_rows": 0,
        # decode dispatches made while an earlier program's sampled
        # tokens were still unread: the host ran ahead of the device
        "steps_overlapped": 0,
        # quantized-serving family (ISSUE 9): quantized matmuls executed,
        # KV bytes the int8 pool saved vs the same pool at compute
        # dtype, and fused dequant kernel INSTANTIATIONS — the inc
        # fires at trace time, once per kernel per compiled executable,
        # so it answers "did the Pallas path engage in what XLA built?"
        # not "how many steps ran" (0 off-TPU: the lax fallback serves)
        "quant_matmuls": 0, "kv_quant_bytes_saved": 0,
        "dequant_kernel_calls": 0,
        # Pallas paged-attention kernel instantiations, fp and int8
        # pools alike (same trace-time meaning; 0 off-TPU)
        "paged_kernel_calls": 0,
        # the paged differential-attention kernel, likewise (the
        # phi4flash family: one a window, full and cross layer kind)
        "paged_diff_kernel_calls": 0,
        # the deepseek_v3 family's, likewise: the experts' grouped matmul
        # (two a layer: gate-up, down), the prefill wave's flash forward
        "grouped_matmul_kernel_calls": 0, "flash_prefill_kernel_calls": 0,
        # expert-layer family (models/deepseek_v3.py; zero elsewhere):
        # what the decode step counts on the device and hands back
        # with its sampled tokens — assignments routed by the active
        # slots, distinct experts hit and the fullest expert's load,
        # each summed over expert layers and decode steps
        "moe_assignments": 0, "moe_experts_touched": 0,
        "moe_max_expert_load": 0,
        # speculative-decoding family (SpeculativeServingEngine,
        # ISSUE 13; zero on non-speculative engines): candidates the
        # drafter proposed, how many of those the verify accepted /
        # rejected, and verify dispatches (each commits accepted+1
        # tokens: the longest accepted draft prefix plus the bonus
        # token the verify's own logits supply)
        "drafted_tokens": 0, "accepted_tokens": 0,
        "rejected_tokens": 0, "spec_steps": 0,
        "spec_draft_compiles": 0,
        # prefill/decode disaggregation family (ISSUE 15; zero on
        # unified engines): KV page extractions shipped off a prefill
        # engine, injections landed on a decode engine, the bytes that
        # crossed, and the extract/inject executable acquisitions
        "kv_extracts": 0, "kv_injects": 0, "kv_handoff_bytes": 0,
        "handoff_compiles": 0,
        # fleet-scale KV tiering family (ISSUE 17; zero without a host
        # tier): device pages spilled into the host-RAM tier and their
        # bytes, pages faulted BACK into the device pool on a prefix
        # hit, no-prefill fault-back admissions, and host entries whose
        # content-hash verification REJECTED them (corrupt bytes are
        # dropped and the request re-prefills — never served)
        "pages_spilled": 0, "spill_bytes": 0,
        "pages_faulted_back": 0, "fault_backs": 0,
        "fault_back_rejects": 0})


def _legacy_counter(engine, key):
    """compile_cache ``legacy_inc`` adapter: an executable acquisition
    (build OR artifact load) counts into the engine's dual (global
    serving.* family + per-engine) legacy counter — the aliased view
    the bench's ladder/compile bounds read."""
    def inc(event):
        if event == "build":
            engine._inc(key)
    return inc


class Request:
    """One generation request's lifecycle record.

    ``request_id`` is the request's STABLE identity: client-suppliable
    (any hashable — a router retrying across replicas reuses the same id
    so completions dedupe), auto-assigned a uuid4 hex otherwise.  It
    travels into ``serving_step`` / ``request_complete`` JSONL events
    and the latency-histogram labels, so telemetry from different
    replicas joins on it."""

    def __init__(self, prompt, max_new_tokens, eos_token=None,
                 request_id=None):
        self.id = request_id if request_id is not None else uuid.uuid4().hex
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self.eos_token = eos_token
        self.tokens = []            # generated ids (python ints)
        self.logits = None          # per-token [V] rows when captured
        self.slot = None
        self.preemptions = 0        # page-exhaustion evictions survived
        # prefill/decode disaggregation (ISSUE 15): a prefill-only
        # request finishes at admission with its prompt's KV pages
        # extracted onto ``kv_payload`` (reason "prefill_done"); an
        # injected request carries the shipped pages in ``_inject``
        # until the decode engine scatters them into its pool
        self.prefill_only = False
        self.kv_payload = None      # host arrays, one per pool operand
        self._inject = None         # shipped pages awaiting injection
        self._inject_tok = None     # the prefill's first sampled token
        # speculative engine's per-row pending-draft state (ISSUE 13):
        # committed tokens the draft model has not ingested yet (None
        # until the spec engine activates the row).  MUST be scrubbed on
        # retry — a preempted-then-retried request re-prefills the draft
        # cache from its prompt, and stale ctx would double-feed tokens
        self.pending_draft = None
        self.done = False
        self.failed = False         # aborted mid-step; re-queueable
        self.error = None           # the abort's diagnosis when failed
        self.finish_reason = None   # "length" | "eos"
        self.submit_t = time.perf_counter()
        self.finish_t = None
        # perf_counter() reading per NEW generated position, stamped by
        # the engine when it appends the token (the moment a streaming
        # client could be sent it).  Survives reset_for_retry: positions
        # regenerated after a preemption are not news.
        self.token_t = []
        # distributed tracing (ISSUE 19): the router mints this at
        # admission and ships it on every RPC hop; engine-side span
        # events carry it so cross-process assembly stitches one
        # lifecycle.  Direct (non-fleet) engine use mints its own when
        # tracing is on — a fleet worker overwrites it with the
        # router's id before any span event fires.
        self.trace_id = tracing.mint() if tracing.enabled() else None

    @property
    def output(self):
        """prompt + generated ids as one int32 array."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int32)])

    @property
    def first_token_t(self):
        """When the first generated token was appended (None before)."""
        return self.token_t[0] if self.token_t else None

    def latency(self):
        return (self.finish_t - self.submit_t) if self.done else None

    def reset_for_retry(self):
        """Scrub generation state so the SAME Request (same id, same
        limits) can be re-queued from scratch after a mid-step abort or
        a page-exhaustion preemption — greedy decoding makes the retry
        token-exact with a run that never failed.  ``preemptions``
        survives on purpose (it is the retry's audit trail), and so does
        ``token_t`` (the client already has those positions)."""
        self.tokens = []
        self.logits = None
        self.slot = None
        self.pending_draft = None
        self.kv_payload = None      # a retried prefill re-extracts
        self.done = False
        self.failed = False
        self.error = None
        self.finish_reason = None
        self.finish_t = None
        return self


class ServingEngine:
    """Continuous-batching greedy decoder over a GPT functional core.

    ``model``: a ``models.gpt.GPT`` Layer, or a ``(params_pytree, cfg)``
    pair (raw jax arrays).  Knobs:

    * ``slots`` — in-flight sequence pool size (the decode batch).
    * ``max_len`` — per-slot KV capacity; admission requires
      ``len(prompt) + max_new_tokens <= max_len``.
    * ``seq_buckets`` / ``batch_buckets`` — the prefill shape ladder;
      total prefill executables are bounded by
      ``len(seq_buckets) * len(batch_buckets)``.
    * ``max_queue`` — bounded admission queue (default ``8 * slots``);
      beyond it :meth:`submit` raises :class:`ServingQueueFull`.
    * ``capture_logits`` — keep each request's per-token fp32 logit rows
      (parity tests / bench; costs a host fetch per step).
    * ``quant`` — weight-only quantization mode (``"int8"``,
      ``"int8_dynamic"``, ``"fp8"``; see models/gpt.py::quantize_params):
      the param pytree is quantized once at construction and every
      executable runs its matmuls through the fused dequant path.
      Accuracy is a budget, not exact parity — gate on the bench's
      logit-error check.

    Decoding is greedy (the parity contract with
    ``models.gpt.generate(temperature=0)``).
    """

    def __init__(self, model, *, slots=4, max_len=None, seq_buckets=None,
                 batch_buckets=DEFAULT_BATCH_BUCKETS, max_queue=None,
                 capture_logits=False, cache_dtype=None, quant=None,
                 tp=None, pp=None):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp

        if isinstance(model, (tuple, list)) and len(model) == 2:
            params, cfg = model
        else:
            cfg = model.cfg
            from ..ops import dispatch as _dispatch
            params = _dispatch.unwrap(model._tree())
        self.cfg = cfg
        self._family = family_of(cfg)
        self._family.check_serving(
            cfg, engine=type(self).__name__, quant=quant,
            tp=tp or os.environ.get("PADDLE_SERVE_TP") or 1,
            pp=pp or os.environ.get("PADDLE_SERVE_PP") or 1)
        # weight-only quantization (ISSUE 9): the param pytree is
        # quantized ONCE here — every executable built below closes over
        # int8/fp8 weights + scales as ordinary pytree operands, and
        # models/gpt.py::block_apply routes their matmuls through the
        # fused dequant path.  Orthogonal to the paged engine's
        # kv_dtype: quant shrinks the weights, kv_dtype the KV pool.
        self.quant = quant
        self._kv_dtype = None          # the paged subclass may set int8
        if quant is not None:
            params = gpt.quantize_params(params, quant)
        # tensor-parallel serving (ISSUE 15): ``tp`` (env fallback
        # PADDLE_SERVE_TP) places the params with the megatron
        # column/row rules from distributed/auto/rules.py and shards
        # the KV pool's head axis over a 1-D 'tp' mesh; the executables
        # below stay the same jnp programs — GSPMD partitions them from
        # the operand shardings, so a model whose fp32 weights exceed
        # one device serves with each rank holding ~1/tp of the bytes.
        if tp is None:
            tp = os.environ.get("PADDLE_SERVE_TP") or 1
        self._tp = int(tp)
        if self._tp < 1:
            raise ValueError(f"tp must be >= 1, got {self._tp}")
        # pipeline-stage serving (ISSUE 20): ``pp`` (env fallback
        # PADDLE_SERVE_PP) adds a leading 'pp' mesh axis — the stacked
        # layer axis of every block param AND of the KV pools splits
        # across stages, and the paged executables run the 1F1B
        # microbatch schedule (distributed/auto/pipeline.py) inside the
        # one donated step, handing activations between stages with
        # ppermute.
        if pp is None:
            pp = os.environ.get("PADDLE_SERVE_PP") or 1
        self._pp = int(pp)
        if self._pp < 1:
            raise ValueError(f"pp must be >= 1, got {self._pp}")
        if self._pp > 1 and type(self) is ServingEngine:
            # the 1F1B stage loop lives in the paged builders only; the
            # slot engine has no pp path (and silently ignoring the
            # knob would void the per-stage memory claim)
            raise ValueError("pp > 1 needs the paged engine — "
                             "use PagedServingEngine(pp=...)")
        self._mesh = None
        self._param_specs = None
        if self._tp > 1 or self._pp > 1:
            if self._tp > 1 and cfg.num_heads % self._tp:
                raise ValueError(
                    f"num_heads {cfg.num_heads} must divide by tp "
                    f"{self._tp} — the KV pool shards on the head axis")
            if (self._tp > 1 and getattr(cfg, "moe_experts", 0)
                    and cfg.moe_experts % self._tp):
                raise ValueError(
                    f"moe_experts {cfg.moe_experts} must divide by tp "
                    f"{self._tp} — expert MLPs shard WHOLE over the tp "
                    "axis (expert parallelism)")
            if self._pp > 1 and cfg.num_layers % self._pp:
                raise ValueError(
                    f"num_layers {cfg.num_layers} must divide by pp "
                    f"{self._pp} — stages take contiguous equal layer "
                    "ranges (distributed/auto/pipeline.py)")
            self._mesh = gpt.serving_mesh(self._tp, pp=self._pp)
            params, self._param_specs = (
                self._family.shard_params_for_serving(params, cfg,
                                                      self._mesh))
        self._kv_spec = self._family.kv_pool_spec(self._mesh)
        self.params = params

        self.slots = int(slots)
        self.max_len = int(max_len or cfg.max_seq_len)
        if self.max_len > cfg.max_seq_len:
            raise ValueError(f"max_len {self.max_len} exceeds "
                             f"cfg.max_seq_len {cfg.max_seq_len}")
        if seq_buckets is None:
            seq_buckets = _pow2_ladder(min(16, self.max_len), self.max_len)
        self.seq_buckets = tuple(sorted(int(s) for s in seq_buckets))
        if self.seq_buckets[-1] > self.max_len:
            raise ValueError(f"seq bucket {self.seq_buckets[-1]} exceeds "
                             f"max_len {self.max_len}")
        self.batch_buckets = tuple(sorted(int(b) for b in batch_buckets))
        self.max_queue = int(max_queue if max_queue is not None
                             else 8 * self.slots)
        self.capture_logits = bool(capture_logits)
        # speculative-decoding identity (the spec subclass overrides;
        # part of the fleet numeric/behavior contract attestation)
        self.spec_mode = None
        self.spec_k = None

        # a restart re-loads yesterday's executables (no-op unless
        # JAX_COMPILATION_CACHE_DIR or PADDLE_JIT_CACHE_DIR names a
        # directory — the fixed default belongs to entry-point scripts)
        jax_compat.enable_persistent_cache()
        timeline.install_compile_hook()

        self._cache_dtype = cache_dtype
        self._rebuild_cache()
        # host-side bookkeeping mirrors: authoritative for scheduling
        self._lens = np.zeros((self.slots,), np.int32)
        self._active = np.zeros((self.slots,), bool)
        self._last_tok = np.zeros((self.slots,), np.int32)
        self._slot_req = [None] * self.slots
        self._queue = collections.deque()

        self._stats = _stats_family()
        # the serving.* family is process-global (all engines share the
        # registry cells); _inc mirrors every count into THIS engine's
        # own dict, which stats() reports — a global-delta snapshot would
        # misattribute a coexisting engine's traffic
        self._counts = {k: 0 for k in self._stats}
        # the kernels' engagement counters fire where a program is
        # TRACED, deep under the family's code, so stats() reports what
        # the process counted since this engine was built (its own
        # programs, unless another engine traces meanwhile)
        self._kernels_before = {k: self._stats[k]
                                for k in self._KERNEL_COUNTERS}
        self._prefill = _cc.site(
            "serving.prefill",
            maxsize=4 * len(self.seq_buckets) * len(self.batch_buckets),
            legacy_inc=_legacy_counter(self, "prefill_compiles"))
        self._decode_site = _cc.site("serving.decode", maxsize=4)
        self._decode_jit = None
        self._g_queue = metrics.gauge("serving.queue_depth")
        self._g_occ = metrics.gauge("serving.slot_occupancy")
        self._g_occ_peak = metrics.gauge("serving.slot_occupancy_peak")
        self._h_prefill = metrics.histogram("serving.prefill_s")
        self._h_decode = metrics.histogram("serving.decode_step_s")
        self._h_ttft = metrics.histogram("serving.ttft_s")
        self._h_gap = metrics.histogram("serving.token_gap_s")
        # a fleet replica labels its latency series with its replica id
        # (PADDLE_FLEET_REPLICA, set by the router) so per-replica
        # latency joins across the fleet's merged telemetry
        self._replica = os.environ.get("PADDLE_FLEET_REPLICA")
        self._engine_id = next(_ENGINE_IDS)
        self._h_req = metrics.histogram(
            "serving.request_latency_s",
            **({"replica": self._replica} if self._replica else {}))
        self._aborted = []          # mid-step abort victims, until taken
        self._admitting = []        # requests inside the current prefill
        self._finished_backlog = []  # finished, not yet handed to a caller
        self._step_idx = 0          # engine iterations, warm-up included
        self._occ_peak = 0
        self._warming = False

    def _rebuild_cache(self):
        """(Re)allocate the KV pool — called at construction and by
        :meth:`_abort_inflight` (a failed donated dispatch consumed the
        old buffers).  The paged subclass overrides this with the page
        pool + allocator reset."""
        cache = gpt.init_slot_cache(self.cfg, self.slots, self.max_len,
                                    dtype=self._cache_dtype,
                                    mesh=self._mesh)
        self._cache_k, self._cache_v = cache["k"], cache["v"]

    # ------------------------------------------------------------- intake
    _UNSET = object()

    def submit(self, prompt, max_new_tokens=_UNSET, eos_token=_UNSET,
               request_id=_UNSET):
        """Queue one request; returns its :class:`Request` handle.
        ``prompt`` is a token array (``max_new_tokens`` defaults to 16)
        or a prepared :class:`Request` — whose limits travel ON it, so
        passing them here too would be silently dropped and raises
        instead.  Raises :class:`ServingQueueFull` past ``max_queue``
        queued (the pool's in-flight slots don't count — they drain on
        their own)."""
        U = self._UNSET
        if isinstance(prompt, Request):
            if (max_new_tokens is not U or eos_token is not U
                    or request_id is not U):
                raise ValueError(
                    "submit(Request, ...) ignores per-call limits — set "
                    "max_new_tokens/eos_token/request_id on the Request "
                    "itself")
            req = prompt
            # latency is measured from ENQUEUE: a Request prepared long
            # before submission must not report its idle time as serving
            req.submit_t = time.perf_counter()
        else:
            req = Request(prompt,
                          16 if max_new_tokens is U else max_new_tokens,
                          None if eos_token is U else eos_token,
                          None if request_id is U else request_id)
        need = len(req.prompt) + req.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request needs {need} cache positions "
                f"(prompt {len(req.prompt)} + {req.max_new_tokens} new) "
                f"> max_len {self.max_len}")
        if req.prefill_only and not getattr(self, "_handoff", False):
            raise ValueError(
                "prefill-only admission needs a "
                "PagedServingEngine(kv_handoff=True) — this engine has "
                "no page-extraction path")
        self._check_prompt(req)
        # the bound covers EVERY admission queue (_queued_total: the
        # paged engine's injection queue included) — the gauge, stats()
        # and this check must agree on what "queued" means
        if self._queued_total() >= self.max_queue:
            self._inc("queue_rejects")
            raise ServingQueueFull(
                f"queue depth {self._queued_total()} at max_queue "
                f"{self.max_queue}")
        self._queue.append(req)
        self._g_queue.set(self._queued_total())
        return req

    def _check_prompt(self, req):
        """Reject prompts the engine can NEVER serve (a named fast
        failure beats bouncing them forever).  The paged subclass
        relaxes the bucket bound for chunk-eligible prompts."""
        if len(req.prompt) > self.seq_buckets[-1]:
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds the largest "
                f"prefill bucket {self.seq_buckets[-1]}")

    # ------------------------------------------------------- bucket maths
    def _seq_bucket(self, n):
        for b in self.seq_buckets:
            if n <= b:
                return b
        raise ValueError(f"no seq bucket fits prompt length {n}")

    def _batch_bucket(self, n):
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    # --------------------------------------------------------- executables
    _n_cache = 2          # KV pool operands per executable (paged: 2|4)

    def _donate(self, first=1):
        """donate_argnums for an executable whose KV pool operands sit
        at positions ``first .. first + n_cache - 1`` — the ONE place
        the donation signature is computed, so the site keys, the AOT
        stable keys and the built executables can never disagree."""
        return (tuple(range(first, first + self._n_cache))
                if _donation_enabled() else ())

    def _aot_sig(self):
        """Cross-process-stable identity of every executable this engine
        builds: the model config plus every knob that changes program
        SHAPES or structure (never param values — params are operands,
        so artifacts are shared across seeds and checkpoints).  The
        artifact store additionally stamps jax version + backend."""
        import dataclasses
        cfg = dataclasses.asdict(self.cfg)
        cfgs = ",".join(f"{k}={cfg[k]}" for k in sorted(cfg))
        return (f"cfg[{cfgs}]/quant={self.quant}/kv={self._kv_dtype}"
                f"/cap={int(self.capture_logits)}/slots={self.slots}"
                f"/max_len={self.max_len}/cdt={self._cache_dtype}"
                f"/donate={int(_donation_enabled())}/tp={self._tp}"
                f"/pp={self._pp}")

    def _aot_key(self, kind, **extra):
        ex = "".join(f"/{k}={v}" for k, v in sorted(extra.items()))
        return f"serving/{kind}/{self._aot_sig()}{ex}"

    def _mesh_key(self):
        """Mesh-topology part folded into every compile-cache key
        (ISSUE 15): a sharded executable on a different mesh is a
        different program.  None on single-device engines, so their
        keys are byte-identical to the pre-TP era."""
        if self._mesh is None:
            return None
        devs = self._mesh.devices.reshape(-1)
        if self._pp > 1:
            return ("pp", self._pp, "tp", self._tp,
                    devs[0].platform, len(devs))
        # pp == 1 keys stay byte-identical to the pre-pp era so
        # yesterday's tp artifacts survive the field's introduction
        return ("tp", self._tp, devs[0].platform, len(devs))

    def _topology(self):
        """The artifact-header device-topology attestation: the AOT
        store rejects (as stale, rebuilt) a sharded executable
        deserialized onto a mismatched mesh; single-device artifacts
        carry None and stay valid across the field's introduction."""
        mk = self._mesh_key()
        return None if mk is None else "/".join(str(p) for p in mk)

    def _constrain_cache(self, arrs):
        """Pin KV-pool outputs to the pool sharding inside the jitted
        builders, so every executable's output sharding provably equals
        its input's.  Donated dispatches already guarantee it (aliased
        buffers share a layout); on the non-donated CPU path GSPMD
        propagation USUALLY agrees — this makes it an invariant, not a
        habit.  No-op on single-device engines."""
        if self._mesh is None:
            return tuple(arrs)
        return tuple(jax_compat.with_sharding_constraint(
            a, self._mesh, self._kv_spec) for a in arrs)

    def param_bytes_per_device(self):
        """Bytes of the (possibly tp-sharded) param pytree each device
        actually pins — the bench's serves-past-one-device proof."""
        from ..distributed.auto import rules
        return rules.bytes_per_device(self.params)

    def _cache_operands(self):
        """The KV pool arrays in executable-operand order (the paged
        subclass overrides with the page pool, + scales on int8)."""
        return (self._cache_k, self._cache_v)

    @staticmethod
    def _bytes_on(dev, tree):
        """Bytes of ``tree`` pinned on ONE device: the shard that lives
        there for sharded leaves, the full copy for replicated ones."""
        import jax
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                for sh in shards:
                    if sh.device == dev:
                        total += (sh.data.size
                                  * np.dtype(sh.data.dtype).itemsize)
            else:
                total += leaf.size * np.dtype(leaf.dtype).itemsize
        return total

    def stage_bytes(self):
        """Per-pipeline-stage memory proof: what ONE device of each
        stage row actually pins — params + KV pool (the int8 scale
        arrays ride both: weight scales in the param tree, KV scales in
        the cache operands) — so the over-budget bench assertion is
        honest about what each device holds.  A pp==1 engine reports
        one stage covering everything."""
        from ..distributed.auto import rules
        if self._mesh is None or self._pp == 1:
            return [{"params": rules.bytes_per_device(self.params),
                     "kv": rules.bytes_per_device(
                         list(self._cache_operands()))}]
        grid = self._mesh.devices        # [pp, tp]
        out = []
        for s in range(self._pp):
            dev = grid[s].reshape(-1)[0]
            out.append({
                "params": self._bytes_on(dev, self.params),
                "kv": self._bytes_on(dev, list(self._cache_operands()))})
        return out

    def _build_prefill(self, b, s):
        """One prefill executable per (batch, seq) bucket: runs the causal
        forward over the padded prompts, scatters each row's K/V into its
        slot of the DONATED pool buffer, and samples each row's first
        token from the logits at its true last position."""
        jax, jnp = self._jax, self._jnp
        cfg = self.cfg

        cap = self.capture_logits

        def prefill(params, cache_k, cache_v, tokens, lens, slot_ids):
            fresh = gpt.init_cache(cfg, b, s, dtype=cache_k.dtype)
            logits, filled = gpt.forward_cached(params, tokens, cfg, fresh)
            for r in range(b):          # b is static: unrolled scatter
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, filled["k"][:, r:r + 1],
                    (0, slot_ids[r], 0, 0, 0))
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, filled["v"][:, r:r + 1],
                    (0, slot_ids[r], 0, 0, 0))
            cache_k, cache_v = self._constrain_cache((cache_k, cache_v))
            idx = jnp.clip(lens - 1, 0, s - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]      # [b, V]
            first_tok = jnp.argmax(last, -1).astype(jnp.int32)
            # a fp32 [b, V] output nobody reads is dead HBM traffic on
            # the hot path — only materialize it when capturing
            if cap:
                return cache_k, cache_v, first_tok, last
            return cache_k, cache_v, first_tok

        donate = (1, 2) if _donation_enabled() else ()
        return jax.jit(prefill, donate_argnums=donate)

    def _build_decode(self):
        jax, jnp = self._jax, self._jnp
        cfg = self.cfg

        cap = self.capture_logits

        def decode(params, cache_k, cache_v, lens, toks, active):
            cache = {"k": cache_k, "v": cache_v, "len": lens}
            logits, cache = gpt.decode_step_slots(params, toks, cfg, cache,
                                                  active)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            ck, cv = self._constrain_cache((cache["k"], cache["v"]))
            if cap:
                return ck, cv, nxt, logits
            return ck, cv, nxt

        donate = (1, 2) if _donation_enabled() else ()
        return jax.jit(decode, donate_argnums=donate)

    # ----------------------------------------------------------- scheduling
    def _free_slots(self):
        return [i for i in range(self.slots) if not self._active[i]]

    def _admit(self):
        """Move queued requests into free slots, one prefill wave per
        contiguous same-seq-bucket run (padded to the batch ladder).
        Requests finishing DURING admission — the prefill's first
        sampled token can already satisfy ``max_new_tokens=1`` or hit
        ``eos_token`` — land on the finished backlog like any other."""
        jnp = self._jnp
        while self._queue and not self._active.all():
            free = self._free_slots()
            group, sbucket = [], None
            while (self._queue and len(group) < len(free)
                   and len(group) < self.batch_buckets[-1]):
                nxt_b = self._seq_bucket(len(self._queue[0].prompt))
                if sbucket is None:
                    sbucket = nxt_b
                elif nxt_b != sbucket:
                    break           # next wave picks it up
                group.append(self._queue.popleft())
            if not group:
                break
            bbucket = self._batch_bucket(len(group))
            toks = np.zeros((bbucket, sbucket), np.int32)
            lens = np.ones((bbucket,), np.int32)   # pad rows: len 1
            slot_ids = np.zeros((bbucket,), np.int32)
            scratch = free[0]       # pad rows scatter over a row that a
            for r, req in enumerate(group):        # real row rewrites
                toks[r, :len(req.prompt)] = req.prompt
                lens[r] = len(req.prompt)
                slot_ids[r] = free[r]
                req.slot = free[r]
            for r in range(len(group), bbucket):
                slot_ids[r] = scratch
            if len(group) < bbucket:
                # a pad row writing AFTER a real row would clobber that
                # slot: scatter pads first (loop order in the executable
                # is row order), i.e. pads must come first.  Rows are
                # written in order r=0..b-1, so point pads at the scratch
                # slot and ensure the real row for that slot comes later.
                order = list(range(len(group), bbucket)) \
                    + list(range(len(group)))
                toks = toks[order]
                lens = lens[order]
                slot_ids = slot_ids[order]
                group_rows = {id(req): order.index(r)
                              for r, req in enumerate(group)}
            else:
                group_rows = {id(req): r for r, req in enumerate(group)}

            # visible to _abort_inflight: these requests left the queue
            # but are not in _slot_req yet — a prefill failure must mark
            # them re-queueable too, not silently lose them
            self._admitting = group
            if tracing.enabled() and not self._warming:
                for req in group:
                    tracing.event(
                        "queue_wait", trace_id=req.trace_id,
                        request_id=req.id, batch=bbucket, seq=sbucket,
                        wait_s=round(
                            time.perf_counter() - req.submit_t, 6))
            with timeline.span("serving.prefill_operands"):
                donate = self._donate()
                operands = (self.params, self._cache_k, self._cache_v,
                            jnp.asarray(toks), jnp.asarray(lens),
                            jnp.asarray(slot_ids))
                fn = self._prefill.get(
                    _cc.make_key(bbucket, sbucket, donate=donate,
                                 mesh=self._mesh_key()),
                    lambda: self._build_prefill(bbucket, sbucket),
                    stable_key=self._aot_key("prefill", b=bbucket,
                                             s=sbucket),
                    example_args=operands, topology=self._topology())
            # the wave span IS the serving.prefill_s interval: from the
            # jitted call to the end of the first-token commit loop
            with timeline.span("serving.prefill_wave", batch=bbucket,
                               seq=sbucket,
                               request_ids=[r.id for r in group]) as wave:
                with timeline.span("serving.prefill_wave.dispatch"):
                    out = fn(*operands)
                if self.capture_logits:
                    (self._cache_k, self._cache_v, first_tok,
                     last_logits) = out
                else:
                    self._cache_k, self._cache_v, first_tok = out
                self._inc("prefill_calls")
                self._count_quant_matmuls()
                with timeline.span("serving.prefill_wave.readback"):
                    # capture_logits debug mode: the caller asked for
                    # host logits; off by default
                    # ptl: disable-next=PTL004 -- capture_logits debug mode
                    logits_np = (np.asarray(last_logits)
                                 if self.capture_logits else None)
                    # sampled-first-token readback: the one designed
                    # sync point of the prefill wave
                    # ptl: disable-next=PTL004 -- sampled-first-token readback
                    first_np = np.asarray(first_tok)
                for req in group:
                    r = group_rows[id(req)]
                    s = req.slot
                    self._lens[s] = len(req.prompt)
                    self._active[s] = True
                    self._slot_req[s] = req
                    self._append_token(req, int(first_np[r]),
                                       logits_np[r] if logits_np is not None
                                       else None)
                    self._last_tok[s] = int(first_np[r])
                    self._inc("requests_admitted")
                    # not during warmup: the quiet counters don't advance
                    # there, so a step/request-scoped fault would see the
                    # same index forever and fire at boot
                    if _faults.active() and not self._warming:
                        _faults.replica_kill_check(
                            request=self._counts["requests_admitted"])
                self._admitting = []
            if not self._warming:
                self._h_prefill.observe(wave.dur)
        self._g_queue.set(self._queued_total())
        occ = int(self._active.sum())
        self._g_occ.set(occ)
        if not self._warming:
            self._occ_peak = max(self._occ_peak, occ)
            if occ > self._g_occ_peak.value:
                self._g_occ_peak.set(occ)

    def _append_token(self, req, tok, logits_row):
        req.tokens.append(tok)
        if not self._warming and len(req.tokens) > len(req.token_t):
            # one stamp per NEW position: after a preemption the engine
            # regenerates positions the client already has
            now = time.perf_counter()
            if req.token_t:
                self._h_gap.observe(now - req.token_t[-1])
            else:
                self._h_ttft.observe(now - req.submit_t)
            req.token_t.append(now)
        if self.capture_logits:
            if req.logits is None:
                req.logits = []
            # logits_row is the already-synced host copy (logits_np
            # slice), not a device value
            # ptl: disable-next=PTL004 -- already-synced host copy
            req.logits.append(np.asarray(logits_row, np.float32))
        self._inc("tokens_generated")
        if (req.eos_token is not None and tok == req.eos_token):
            self._finish(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length")

    def _append_tokens(self, req, toks, logits_rows=None):
        """Multi-token commit (ISSUE 13): append an accepted speculative
        window's tokens in order, stopping at the first finishing token
        (eos / length — the device-side commit math already truncates
        there, so the guard is defensive).  ``logits_rows`` is the
        already-synced [W, V] host block when capturing.  Returns how
        many were appended."""
        n = 0
        for i, tok in enumerate(toks):
            self._append_token(req, int(tok),
                               logits_rows[i] if logits_rows is not None
                               else None)
            n += 1
            if req.done:
                break
        return n

    def _finish(self, req, reason):
        req.done = True
        req.finish_reason = reason
        req.finish_t = time.perf_counter()
        # completions ride a backlog drained by step()/take_finished():
        # a request finishing inside a step that LATER raises must still
        # reach the caller (the fleet worker reports it to the router) —
        # returning step-local lists would drop it with the exception
        self._finished_backlog.append(req)
        if not self._warming:
            self._h_req.observe(req.finish_t - req.submit_t)
            if timeline.telemetry_dir():
                timeline.emit({"event": "request_complete",
                               "request_id": str(req.id),
                               "replica": self._replica,
                               # per-process total order + emitter id
                               # (ISSUE 19): trace assembly sorts on seq
                               # at equal timestamps
                               "seq": tracing.seq(),
                               "engine": self._engine_id,
                               "latency_s": round(
                                   req.finish_t - req.submit_t, 6),
                               "tokens": len(req.tokens),
                               "finish_reason": reason})
            # distinct names per phase outcome: trace assembly uses the
            # FIRST "completion" as the decode-end boundary, so the
            # disagg prefill leg's finish must not shadow it
            tracing.event("prefill_done" if reason == "prefill_done"
                          else "completion",
                          trace_id=req.trace_id, request_id=req.id,
                          finish_reason=reason, tokens=len(req.tokens),
                          engine=self._engine_id)
        if req.slot is not None:
            s = req.slot
            self._active[s] = False
            self._slot_req[s] = None
            gpt.reset_slots(self._lens, s)
        self._inc("requests_completed")

    # ------------------------------------------------------------- driving
    def step(self):
        """One engine iteration: admit from the queue into free slots,
        then one slot-batched decode step.  Returns the requests that
        FINISHED this iteration (their slots are already free — the next
        ``step()`` re-admits from the queue: continuous batching).

        If the step raises mid-flight (device error, injected
        ``engine_error`` fault), every in-flight request is ABORTED
        rather than leaked: its slot is freed, the KV pool is rebuilt
        (a failed donated dispatch may have consumed the buffers), and
        the request is marked ``failed``/re-queueable and parked in
        :meth:`take_aborted` — occupancy recovers instead of pinning
        dead slots forever.  The original exception still propagates;
        requests that COMPLETED before the failure stay on the finished
        backlog and come back from the next ``step()`` /
        :meth:`take_finished` — a crash after a completion never
        un-completes it."""
        self._step_idx += 1
        try:
            with timeline.span("serving.step", step=self._step_idx):
                self._step_inner()
        except Exception as e:
            self._abort_inflight(e)
            raise
        return self.take_finished()

    def take_finished(self):
        """Drain the finished-request backlog (normally what ``step()``
        just returned; after a step that RAISED, the requests that
        completed before the failure)."""
        out, self._finished_backlog = self._finished_backlog, []
        return out

    def _abort_inflight(self, err):
        """Free every slot and mark the victims re-queueable (the
        slot-leak fix): in-flight requests AND any mid-admission group
        whose prefill failed after leaving the queue."""
        aborted = [r for r in self._slot_req if r is not None]
        aborted += [r for r in self._admitting
                    if r not in aborted and not r.done]
        self._admitting = []
        detail = f"{type(err).__name__}: {err}"
        for req in aborted:
            req.failed = True
            req.error = detail
            req.slot = None
        self._active[:] = False
        self._lens[:] = 0
        self._slot_req = [None] * self.slots
        # rebuild the donated KV pool: the failed dispatch may have
        # consumed (donated) the old buffers, and whatever it scattered
        # is untrusted anyway — every victim restarts from its prompt
        self._rebuild_cache()
        self._g_occ.set(0)
        if aborted:
            self._inc("step_aborts")
            self._inc("requests_aborted", len(aborted))
            self._aborted.extend(aborted)
            # incident flight dump: last-hop ring + the victims' ids —
            # the postmortem names who was in flight, not just a counter
            tracing.dump("engine_abort",
                         inflight=[r.id for r in aborted],
                         extra={"error": detail[:400],
                                "engine": self._engine_id})
        return aborted

    def take_aborted(self):
        """Drain the requests aborted by failed steps since the last
        call — the fleet worker re-queues these (each already
        ``reset_for_retry()``-able; ids are stable so the router
        dedupes)."""
        out, self._aborted = self._aborted, []
        return out

    def active_request_ids(self):
        """Ids this engine still OWNS (queued, mid-admission, or
        holding a decode slot) — the fleet worker's readopt re-hello
        claims exactly these after a router restart.  Parked abort
        victims are excluded on purpose: they need a re-queue, not a
        claim, and the relaunched router's journal replay re-queues
        every unclaimed id anyway."""
        ids = [str(r.id) for r in self._queue]
        ids += [str(r.id) for r in self._admitting if not r.done]
        ids += [str(r.id) for r in self._slot_req if r is not None]
        seen = set()
        return [i for i in ids if not (i in seen or seen.add(i))]

    def cancel(self, request_id):
        """Remove a QUEUED request by id (deadline/cancel path); returns
        the Request or None.  An in-flight request runs to completion —
        callers dedupe/discard its completion by id."""
        for req in self._queue:
            if req.id == request_id:
                self._queue.remove(req)
                self._g_queue.set(self._queued_total())
                self._inc("requests_cancelled")
                return req
        return None

    def _step_inner(self):
        with timeline.span("serving.admit"):
            self._admit()
        if not self._active.any():
            return
        finished = []        # this decode wave's, for the step event
        jnp = self._jnp
        if _faults.active() and not self._warming:
            _faults.engine_step_error(self._counts["decode_steps"] + 1)
            _faults.replica_kill_check(
                step=self._counts["decode_steps"] + 1)
        with timeline.span("serving.decode_operands"):
            operands = (self.params, self._cache_k, self._cache_v,
                        jnp.asarray(self._lens),
                        jnp.asarray(self._last_tok),
                        jnp.asarray(self._active))
            if self._decode_jit is None:
                donate = self._donate()
                self._decode_jit = self._decode_site.get(
                    _cc.make_key("decode", donate=donate,
                                 mesh=self._mesh_key()),
                    self._build_decode,
                    stable_key=self._aot_key("decode"),
                    example_args=operands, topology=self._topology())
                self._inc("decode_compiles")
        # the decode span IS the serving.decode_step_s interval: from the
        # jitted call to the end of the commit loop
        with timeline.span("serving.decode",
                           active=int(self._active.sum())) as decode:
            with timeline.span("serving.decode.dispatch"):
                out = self._decode_jit(*operands)
            if self.capture_logits:
                self._cache_k, self._cache_v, nxt, logits = out
            else:
                self._cache_k, self._cache_v, nxt = out
            self._inc("decode_steps")
            self._count_quant_matmuls()
            with timeline.span("serving.decode.readback"):
                # ptl: disable-next=PTL004 -- capture_logits debug readback
                logits_np = (np.asarray(logits) if self.capture_logits
                             else None)
                # sampled-token readback: THE designed device->host sync
                # of the decode loop (tokens must reach clients)
                # ptl: disable-next=PTL004 -- sampled-token readback
                nxt_np = np.asarray(nxt)
            with timeline.span("serving.decode.commit"):
                for s in range(self.slots):
                    if not self._active[s]:
                        continue
                    req = self._slot_req[s]
                    self._lens[s] += 1
                    self._append_token(req, int(nxt_np[s]),
                                       logits_np[s] if logits_np is not None
                                       else None)
                    self._last_tok[s] = int(nxt_np[s])
                    if req.done:
                        finished.append(req)
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
                           # per-process total order + emitter (ISSUE 19)
                           "seq": tracing.seq(),
                           "engine": self._engine_id,
                           "replica": self._replica,
                           # stable ids: telemetry joins across replicas
                           "finished_ids": [str(r.id) for r in finished]})
        if tracing.enabled() and not self._warming:
            for r in finished:
                tracing.event("decode_iter", trace_id=r.trace_id,
                              request_id=r.id, iters=len(r.tokens),
                              decode_s=round(dt, 6),
                              engine=self._engine_id)

    def _queued_total(self):
        """Requests waiting for admission — the one definition the
        queue-depth gauge AND stats() read (the paged subclass adds its
        injection queue, so a decode-role replica's queued handoffs are
        never reported as an idle engine)."""
        return len(self._queue)

    def _busy(self):
        """Work left to drive?  (The paged subclass adds its
        mid-chunked-prefill jobs, which hold slots without being decode-
        active yet.)"""
        return bool(self._queue) or bool(self._active.any())

    def run(self, max_steps=None):
        """Drive :meth:`step` until the queue and every slot drain.
        Returns all requests finished during the run."""
        out = []
        steps = 0
        while self._busy():
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return out

    # ------------------------------------------------- AOT artifact boot
    def _aot_covered(self):
        """Artifact-warm boot (ISSUE 14): the set of (b, s) prefill
        rungs whose serialized artifacts VALIDATE (header + digest +
        jax/backend match — a merely-existing stale artifact from a
        shared dir after a jax upgrade must not count) — those rungs
        SKIP their dummy compile wave, and the executables load lazily
        at first use (an artifact load is a deserialization, not an XLA
        compile, so the zero-steady-state-compiles invariant holds
        either way).  Empty when no store is active or the CORE
        executables (decode; subclasses add theirs) have no valid
        artifacts — a partial store must not skip the wave that would
        have compiled the missing piece (the degradation contract)."""
        if _cc.artifact_dir() is None:
            return set()
        if not self._aot_has_core():
            return set()
        return {(b, s) for s in self.seq_buckets
                for b in self.batch_buckets
                if _cc.artifact_ready(
                    self._aot_key("prefill", b=b, s=s),
                    topology=self._topology())}

    def _aot_has_core(self):
        """Do the non-ladder executables the warmup waves would compile
        have artifacts?  (decode here; paged adds nothing — its
        chunk/copy warm blocks gate themselves; the speculative engine
        needs verify + draft.)"""
        return _cc.artifact_ready(self._aot_key("decode"),
                                  topology=self._topology())

    def warmup(self, max_new_tokens=2):
        """Compile every ladder executable BEFORE taking traffic: for
        each (batch, seq) bucket pair, run a wave of dummy requests
        shaped exactly to it, plus the decode step.  After this, steady
        serving issues zero new XLA compiles no matter which buckets
        requests land in — and with ``PADDLE_JIT_CACHE_DIR`` set, a
        restarted server's warmup is pure cache reload.  With
        ``PADDLE_AOT_CACHE_DIR`` holding artifacts, warmup degenerates
        further: preloaded rungs are deserialized executables and their
        dummy waves are SKIPPED — zero compiles, near-zero execution
        (the fleet cold-start path).  The synthetic wave is kept OUT of
        the traffic telemetry (latency histograms, tokens/s window,
        occupancy peak, request/step counters) — only the compile
        counters record it — so a consumer's percentiles describe real
        requests, not compile time.  Returns the number of prefill
        executables compiled (artifact loads included — they count as
        acquisitions)."""
        before = self._counts["prefill_compiles"]
        preloaded = self._aot_covered()
        self._warming = True
        # back-pressure is for traffic, not boot: a deliberately small
        # max_queue must not reject the warmup waves (each wave needs its
        # whole group queued at once so it prefills as ONE batch rung)
        real_max_queue = self.max_queue
        self.max_queue = max(real_max_queue, self.slots,
                             self.batch_buckets[-1])
        try:
            lo = 1                  # smallest prompt length in this rung
            for s in self.seq_buckets:
                # a legal request lands in this rung iff even its
                # SHORTEST prompt (lo) leaves room for one generated
                # token; longer warmup prompts shrink max_new_tokens
                # rather than sliding down a rung (prompt 15 / max_new 1
                # on a max_len-16 ladder must still precompile the top
                # bucket)
                mnt = min(max_new_tokens, self.max_len - lo)
                if mnt < 1:
                    continue        # rung unreachable by any admission
                n = self._warmup_wave_len(lo, s, mnt)
                lo = s + 1
                if n is None:
                    continue        # rung unreachable via this path
                prev = 0
                for b in self.batch_buckets:
                    # smallest group size that pads to bucket b; a rung
                    # no group can reach (its floor exceeds the pool)
                    # stays cold
                    wave = prev + 1
                    prev = b
                    if wave > self.slots:
                        continue
                    if (b, s) in preloaded:
                        continue    # artifact-loaded: nothing to compile
                    for _ in range(wave):
                        self.submit(np.ones((n,), np.int32), mnt)
                    self.run()
        finally:
            self._warming = False
            self.max_queue = real_max_queue
        return self._counts["prefill_compiles"] - before

    def _warmup_wave_len(self, lo, s, mnt):
        """Warmup prompt length that lands in bucket rung ``s`` (whose
        shortest admissible prompt is ``lo``), or None if no wave
        prompt can reach the rung.  The paged subclass caps this at
        ``prefill_chunk`` — longer prompts divert to the chunked path
        and would leave the rung cold."""
        return min(s, self.max_len - mnt)

    def reset_occupancy_peak(self):
        """Restart THIS engine's slot-occupancy high-water mark (e.g.
        after a warmup wave, so a measured run's peak reflects ITS
        traffic).  The shared ``serving.slot_occupancy_peak`` gauge is a
        process-wide monotone max — lowering it here would erase a
        coexisting engine's recorded peak."""
        self._occ_peak = int(self._active.sum())

    def generate(self, prompts, max_new_tokens=16, eos_token=None):
        """Batch convenience: submit every prompt, run to drain, return
        the per-prompt generated-token lists in submission order.
        Batches larger than ``max_queue`` are absorbed by stepping the
        engine between submissions (back-pressure is for ONLINE callers
        who can shed; a batch caller just wants the work done)."""
        reqs = []
        for p in prompts:
            while (len(self._queue) >= self.max_queue
                   and self._busy()):
                self.step()         # drain room instead of rejecting
            reqs.append(self.submit(p, max_new_tokens, eos_token))
        self.run()
        return [r.tokens for r in reqs]

    # --------------------------------------------------------------- views
    # traffic counters a warmup wave must not inflate; compile counters
    # stay live (compiling executables is exactly what warmup reports)
    _WARMUP_QUIET = frozenset((
        "prefill_calls", "decode_steps", "requests_admitted",
        "requests_completed", "tokens_generated",
        "prefill_chunks", "prefix_page_hits", "prefix_page_misses",
        "prefill_tokens", "prefill_padded_rows",
        "cow_copies", "preemptions", "steps_overlapped", "quant_matmuls",
        "moe_assignments", "moe_experts_touched", "moe_max_expert_load",
        "drafted_tokens", "accepted_tokens", "rejected_tokens",
        "spec_steps", "kv_extracts", "kv_injects", "kv_handoff_bytes",
        "pages_spilled", "spill_bytes", "pages_faulted_back",
        "fault_backs", "fault_back_rejects"))

    def _count_quant_matmuls(self):
        """One model forward = 4 quantized matmuls per layer (qkv, proj,
        fc1, fc2) when the weights are quantized — counted next to every
        prefill/chunk/decode dispatch so ``serving.quant_matmuls``
        tracks the quantized executables actually running."""
        if self.quant:
            self._inc("quant_matmuls", 4 * self.cfg.num_layers)

    def _inc(self, key, v=1):
        """Count into the process-global serving.* registry family AND
        this engine's own dict — :meth:`stats` reads the latter, so a
        coexisting engine's traffic is never misattributed."""
        if self._warming and key in self._WARMUP_QUIET:
            return
        self._stats.inc(key, v)
        self._counts[key] = self._counts.get(key, 0) + v

    _KERNEL_COUNTERS = tuple(f"{k}_kernel_calls" for k in (
        "dequant", "paged", "paged_diff", "grouped_matmul",
        "flash_prefill"))

    def stats(self):
        """THIS engine's serving.* counters + live gauges, one dict.
        The process-global family (all engines pooled) is
        :func:`serving_stats`."""
        out = dict(self._counts)
        for k, before in self._kernels_before.items():
            out[k] = self._stats[k] - before
        out["queue_depth"] = self._queued_total()
        out["slot_occupancy"] = int(self._active.sum())
        out["slot_occupancy_peak"] = self._occ_peak
        # the numeric contract (fleet routing/hello attests on these: a
        # mixed fp32/int8 fleet must never cross-route)
        out["quant"] = self.quant
        out["kv_dtype"] = self._kv_dtype
        out["spec_mode"] = self.spec_mode
        out["tp"] = self._tp
        out["pp"] = self._pp
        if self._pp > 1:
            out["stage_bytes"] = self.stage_bytes()
        out.update(self._kv_accounting())
        return out

    def _kv_accounting(self):
        """KV-memory accounting (bench.py --serving's kv block): a
        slot-contiguous pool RESERVES its full footprint whether or not
        slots are filled — that over-reservation is exactly what the
        paged subclass's override shrinks."""
        held = int(self._lens.sum())
        return {"kv_bytes_reserved": int(self._cache_k.nbytes
                                         + self._cache_v.nbytes),
                "kv_tokens_held": held}


# --------------------------------------------------------------------------
# host-RAM KV page tier (ISSUE 17 tentpole)
# --------------------------------------------------------------------------

class _HostKVTier:
    """Byte-bounded LRU of spilled KV pages in host RAM — the tier
    UNDER the device page pool.  Entries are keyed by the pager's
    content key and stamped with a blake2b over their exact bytes
    (salted with the engine's numeric contract): a fault-back serves an
    entry only after re-verifying the stamp, so torn host memory can
    never reach the device pool — the per-shard page-byte-determinism
    invariant extends through the tier."""

    def __init__(self, limit_bytes, hash_key=""):
        self.limit = int(limit_bytes)
        self.hash_key = str(hash_key)
        self._ent = collections.OrderedDict()  # key -> [arrays, stamp, t]
        self.bytes = 0
        self.inserts = 0
        self.lru_evictions = 0

    def __len__(self):
        return len(self._ent)

    def __contains__(self, key):
        return key in self._ent

    def _stamp(self, arrays):
        h = hashlib.blake2b(digest_size=16)
        h.update(self.hash_key.encode())
        for a in arrays:
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()

    def put(self, key, arrays):
        """Insert (or refresh) a spilled page's host copy: one array
        per pool operand, stamped NOW.  Oldest entries fall off the LRU
        until the byte bound holds again."""
        old = self._ent.pop(key, None)
        if old is not None:
            self.bytes -= sum(int(a.nbytes) for a in old[0])
        nbytes = sum(int(a.nbytes) for a in arrays)
        self._ent[key] = [list(arrays), self._stamp(arrays),
                          time.perf_counter()]
        self.bytes += nbytes
        self.inserts += 1
        while self.bytes > self.limit and len(self._ent) > 1:
            _, (arrs, _stamp, _t) = self._ent.popitem(last=False)
            self.bytes -= sum(int(a.nbytes) for a in arrs)
            self.lru_evictions += 1

    def fetch(self, key):
        """``(arrays, age_s)`` for a hash-verified entry (refreshed to
        LRU-newest), ``None`` when absent, or the string ``"corrupt"``
        when present but failing verification — the entry is dropped on
        the spot (bad KV is never served, and never re-tried)."""
        ent = self._ent.get(key)
        if ent is None:
            return None
        arrays, stamp, t = ent
        if self._stamp(arrays) != stamp:
            self._ent.pop(key)
            self.bytes -= sum(int(a.nbytes) for a in arrays)
            return "corrupt"
        self._ent.move_to_end(key)
        return arrays, time.perf_counter() - t

    def corrupt(self, key):
        """Testing hook (the ``host_tier_corrupt`` fault): flip one
        byte of the stored copy AFTER its stamp was taken, so the next
        :meth:`fetch` exercises the reject path."""
        ent = self._ent.get(key)
        if ent is None:
            return
        a = ent[0][0]
        flat = a.view(np.uint8).reshape(-1)
        flat[0] ^= 0xFF

    def digests(self, limit=64):
        """Compact digests of the FULL-page chains resident in the
        tier (the host half of the replica's routing sketch)."""
        from .kv_pager import short_digest
        out = []
        for key in self._ent:
            d = short_digest(key)
            if d is not None:
                out.append(d)
        return out[-int(limit):]


# --------------------------------------------------------------------------
# paged engine (ISSUE 8 tentpole)
# --------------------------------------------------------------------------

class _Dispatched:
    """One program the paged engine has enqueued and not read back: the
    array that holds its sampled tokens (the copy to the host already
    started), the requests they belong to, when the enqueue returned,
    and the id of the span it was dispatched under (``span``), which the
    readback's span names: a program's two spans are one record."""
    __slots__ = ("wave", "step", "toks", "logits", "rows", "t_enq",
                 "attrs", "span")

    def __init__(self, wave, step, toks, logits, rows, attrs, span):
        self.wave, self.step = wave, step
        self.toks, self.logits = toks, logits
        self.rows, self.attrs, self.span = rows, attrs, span
        for a in (toks, logits):
            if a is not None:
                a.copy_to_host_async()
        self.t_enq = time.perf_counter()


class PagedServingEngine(ServingEngine):
    """Continuous batching over a **block-table paged KV cache**: the
    contiguous-per-slot pool is replaced by ``num_pages`` fixed
    ``page_size``-token pages plus a per-slot page table
    (inference/kv_pager.py), so the HBM a request pins tracks its
    LENGTH, not ``max_len`` — at a fixed KV byte budget the paged pool
    admits several times the concurrency of the slot pool.  On top:

    * **shared-prefix reuse** (``prefix_cache=True``) — prompt pages are
      content-hashed; a request repeating an earlier system prompt
      re-acquires the same physical pages (zero new allocations, the
      smoke's attested "prefix hit"), released prompt pages are retained
      LRU for future hits, and divergence is copy-on-write.
    * **chunked prefill** (``prefill_chunk=N``) — prompts longer than
      ``N`` are admitted in ``N``-token pieces, ONE piece per engine
      iteration, so in-flight decodes keep producing tokens while a
      long admission trickles in instead of stalling behind one big
      prefill dispatch.  All chunks share one executable (the position
      offset is a traced scalar).
    * the PR-5 invariants survive: ONE buffer-donated jitted decode
      step forever (``decode_compiles == 1``; the page table, write
      coordinates and lengths are traced operands, so churn never
      changes the signature) and token-exact greedy parity with
      ``models.gpt.generate``.

    Attention gathers K/V through the table via
    ops/pallas/paged_attn.py — a Pallas kernel that DMAs exactly the
    referenced pages on TPU, and a pure-lax gather with *identical
    math* to the slot engine's masked attention elsewhere (CPU tier-1).

    Pool exhaustion is never a stall: the NEWEST request is preempted —
    pages freed, request re-queued from its prompt (named in telemetry
    as ``page_exhaustion``, counted in ``preemptions``, stamped on
    ``Request.preemptions``) — and greedy decoding makes its eventual
    retry token-exact.

    * **quantized KV** (``kv_dtype="int8"``, ISSUE 9) — the page pool
      stores K/V int8 with per-position-per-head fp32 scale arrays
      alongside (models/gpt.py::init_paged_pools): prefill and
      chunk scatters quantize on write, decode attention dequantizes on
      read (in-kernel on TPU: ops/pallas/paged_attn.py::
      paged_attention).  ~4x the tokens per KV byte; COW copies
      page+scale pairs; the prefix hash is salted with the numeric
      contract so int8 pages never alias fp pages.  Composes with
      ``quant=`` (weight-only int8/fp8 executables) — together they are
      the quantized serving path the bench gates on an accuracy budget.

    **The loop runs one step ahead of its readbacks.**  The sampled
    tokens stay on the device: every wave and decode program takes the
    ``[slots]`` token vector as an operand and returns the next one, so
    wave -> decode -> decode chains with no host value in between, and a
    ``step()`` call dispatches step n+1 (admission, paging and the
    enqueue run while the device computes step n) BEFORE it blocks on
    step n's tokens and commits them.  Lengths, page tables and the
    pager advance by count at dispatch; a request that ends by length
    is known by count and its slot is not run again, one that ends by
    ``eos_token`` is known at commit only and its slot runs one more
    position, whose token is dropped and whose K/V row lies in a page
    the request still owned.  A finished request is returned by the
    ``step()`` call that COMMITS its last token.  Whatever needs the
    host's view whole — preemption, ``cancel``, a chunked prompt's last
    chunk, injected or faulted-back pages, a prefill-only hand-off,
    fault injection, ``capture_logits`` (every step: the debug mode is
    the synchronous loop), an engine with nothing left to dispatch —
    first reads back and commits what is in flight (:meth:`_drain`);
    ``stats()`` counts ``steps_overlapped`` and ``drains`` by reason.

    Constraints: ``max_len`` must be a page multiple (seq buckets are
    rounded up to page multiples), and ``prefill_chunk`` must divide
    ``max_len`` and fit inside the largest prefill bucket."""

    def __init__(self, model, *, page_size=16, num_pages=None,
                 prefix_cache=True, prefill_chunk=None, kv_dtype=None,
                 kv_handoff=False, host_tier_mb=None, **kw):
        from .kv_pager import KVPager, PagesExhausted  # noqa: F401
        self._KVPager, self._PagesExhausted = KVPager, PagesExhausted
        self._page_size = int(page_size)
        cfg = _cfg_of(model)
        family_of(cfg).check_serving(
            cfg, engine=type(self).__name__, kv_dtype=kv_dtype,
            kv_handoff=kv_handoff,
            host_tier_mb=(host_tier_mb if host_tier_mb is not None
                          else os.environ.get("PADDLE_KV_HOST_TIER_MB")
                          or 0))
        # host-RAM page tier (ISSUE 17): evicted prefix pages spill
        # their bytes (hash-stamped) into a byte-bounded host LRU, and
        # a later prefix hit on the spilled chain faults them back
        # through the donated inject executable WITHOUT re-prefilling.
        # 0 MB (the default) disables the tier entirely.
        if host_tier_mb is None:
            try:
                host_tier_mb = float(
                    os.environ.get("PADDLE_KV_HOST_TIER_MB", "0") or 0)
            except ValueError:
                host_tier_mb = 0.0
        self._host_tier_mb = float(host_tier_mb)
        self._host_tier = None              # built in _rebuild_cache
        self._spill_pending = collections.deque()
        # chain-tail digest -> first sampled token: greedy decoding is
        # deterministic over identical params, so a memoized first
        # token makes the no-prefill fault-back admission token-exact
        self._first_tok_memo = collections.OrderedDict()
        self._g_host_tier = metrics.gauge("serving.host_tier_bytes")
        self._h_reclaim_age = metrics.histogram(
            "serving.reclaim_hit_age_s")
        self._h_chunked = metrics.histogram("serving.prefill_chunked_s")
        # prefill/decode disaggregation (ISSUE 15): kv_handoff=True
        # primes the page extract/inject executables at warmup — a
        # prefill-role replica finishes prefill-only requests with
        # their prompt pages extracted (submit a Request whose
        # ``prefill_only`` is set), a decode-role replica admits
        # shipped pages via :meth:`submit_prefilled`
        self._handoff = bool(kv_handoff)
        self._extract_jit = None
        self._inject_jit = None
        self._extract_site = _cc.site("serving.extract", maxsize=2)
        self._inject_site = _cc.site("serving.inject", maxsize=2)
        self._inject_queue = collections.deque()
        if self._page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (compute dtype) or 'int8', got "
                f"{kv_dtype!r} — float overrides go through cache_dtype")
        if kv_dtype == "int8" and kw.get("cache_dtype") is not None:
            raise ValueError(
                "kv_dtype='int8' and cache_dtype are mutually exclusive "
                "— the int8 pool's storage dtype is fixed (int8 pages + "
                "fp32 scales); drop cache_dtype")
        self._kv_quant = kv_dtype == "int8"
        self._kv_saved_counted = False
        self._num_pages_cfg = None if num_pages is None else int(num_pages)
        self._prefix_cache_on = bool(prefix_cache)
        self._prefill_chunk = None          # set after buckets are known
        self._chunk_jobs = collections.deque()
        self._chunk_slots = set()
        self._copy_jit = None
        self._chunk_jit = None
        self._copy_site = _cc.site("serving.copy", maxsize=2)
        self._chunk_site = _cc.site("serving.chunk", maxsize=2)
        self._admit_seq = 0
        self._drains = collections.Counter()
        self._prefill_by_bucket = {}    # "<batch>x<seq>" -> sums, _count_wave
        super().__init__(model, **kw)
        self._kv_dtype = kv_dtype
        if getattr(self, "_kv_saved_pending", None):
            self._inc("kv_quant_bytes_saved", self._kv_saved_pending)
            self._kv_saved_pending = 0
        ps = self._page_size
        # the gathered page view is maxP*ps == max_len wide, so paged
        # attention sees exactly the slot engine's mask width — that, and
        # identical fallback math, is what keeps parity token-exact
        self.seq_buckets = tuple(sorted(
            {min(-(-b // ps) * ps, self.max_len) for b in self.seq_buckets}))
        if prefill_chunk is not None:
            c = -(-int(prefill_chunk) // ps) * ps
            if c > self.seq_buckets[-1]:
                raise ValueError(
                    f"prefill_chunk {c} exceeds the largest prefill "
                    f"bucket {self.seq_buckets[-1]} — prompts between "
                    "them would be unserveable")
            if self.max_len % c:
                raise ValueError(
                    f"prefill_chunk {c} must divide max_len "
                    f"{self.max_len} (a clamped chunk write would "
                    "corrupt earlier positions)")
            self._prefill_chunk = c
        if self._pp > 1:
            # the 1F1B stage step (models/gpt_pp.py) runs explicit
            # collectives over full-precision dense weights and
            # whole-bucket prefill waves; name each missing composition
            # instead of producing silently-wrong numerics
            if self.quant is not None:
                raise ValueError(
                    "pp > 1 does not compose with quant= yet — the "
                    "stage step has no dequant-matmul path for "
                    "{'qw','scale'} leaves (tp x quant works: "
                    "ServingEngine(tp=N, quant=...))")
            if self._kv_quant:
                raise ValueError(
                    "pp > 1 does not compose with kv_dtype='int8' yet "
                    "— the stage-local pools store the compute dtype")
            if self._prefill_chunk is not None:
                raise ValueError(
                    "pp > 1 prefills whole buckets through the stage "
                    "ring — drop prefill_chunk")
            from ..models import gpt_pp
            gpt_pp.check_pp_config(self.cfg, self._pp)
            # decode microbatching: slots split into pp groups when they
            # divide evenly (keeps every stage busy outside the bubble);
            # otherwise one group — correct, just bubble-bound
            self._pp_microbatch = (self._pp if self.slots % self._pp == 0
                                   else 1)

    # ------------------------------------------------------------ plumbing
    def _aot_sig(self):
        return (f"{super()._aot_sig()}/ps={self._page_size}"
                f"/pages={self._num_pages}/chunk={self._prefill_chunk}")

    def _rebuild_cache(self):
        ps = self._page_size
        if self.max_len % ps:
            raise ValueError(
                f"max_len {self.max_len} must be a multiple of "
                f"page_size {ps}")
        self._pages_per_slot = self.max_len // ps
        num_pages = (self._num_pages_cfg
                     if self._num_pages_cfg is not None
                     else self.slots * self._pages_per_slot + 1)
        self._num_pages = int(num_pages)
        # the prefix hashes are salted with the numeric contract so an
        # int8 pool's pages can never alias an fp pool's (satellite:
        # mixed-fleet prefix keys must not collide across contracts)
        self._pager = self._KVPager(
            self._num_pages, ps, self.slots,
            prefix_cache=self._prefix_cache_on,
            hash_key=f"quant={self.quant or 'none'}"
                     f"/kv={'int8' if self._kv_quant else 'fp'}"
                     f"{self._family.prefix_salt(self.cfg)}")
        # host-tier spill capture rides the pager's eviction hook; the
        # tier itself SURVIVES rebuilds (its entries are content-
        # addressed host bytes, valid independent of device state)
        self._pager.evict_hook = self._on_page_evicted
        # captures still pending against the OLD pool are untrusted
        # after a rebuild (the failed dispatch may have consumed it)
        self._spill_pending.clear()
        if self._host_tier is None and self._host_tier_mb > 0 \
                and self._prefix_cache_on:
            self._host_tier = _HostKVTier(
                int(self._host_tier_mb * (1 << 20)),
                hash_key=self._pager.hash_key)
        # a family that keeps state per slot says how many arrays at the
        # end of its tuple are indexed by slot (FAMILY_INTERFACE)
        count = getattr(self._family, "slot_state_arrays", None)
        self._n_slot_state = int(count(self.cfg)) if count else 0
        self._pools = pools = tuple(self._family.init_paged_pools(
            self.cfg, self._num_pages, ps, dtype=self._cache_dtype,
            mesh=self._mesh, kv_quant=self._kv_quant,
            **({"slots": self.slots} if self._n_slot_state else {})))
        self._pager.slot_state_bytes = sum(
            int(a.nbytes) for a in self._slot_state())
        if self._kv_quant and not self._kv_saved_counted:
            # bytes the int8+scale pool saves vs the SAME pool at
            # the compute dtype (what a rebuild without kv_dtype
            # would have allocated) — counted once, not per rebuild.
            # The first build happens inside the base constructor
            # before the counters exist; park it for __init__'s tail.
            fp_bytes = 2 * (pools[0].size
                            * self._jnp.dtype(self._cache_dtype
                                              or self.cfg.dtype).itemsize)
            q_bytes = sum(int(a.nbytes) for a in pools)
            self._kv_saved_pending = max(0, fp_bytes - q_bytes)
            self._kv_saved_counted = True
        self._tables_np = np.zeros((self.slots, self._pages_per_slot),
                                   np.int32)
        self._chunk_jobs.clear()
        self._chunk_slots.clear()
        # the step in flight (discarded with the pool it wrote):
        # programs enqueued and not read back, in device order; the
        # token vector the last of them returned (None: the host's
        # ``_last_tok`` is whole, and the next dispatch starts from it);
        # decode positions each slot's request may still be given; when
        # the last program's tokens reached the host
        self._inflight = collections.deque()
        self._tok_dev = None
        self._todo = np.zeros((self.slots,), np.int32)
        self._t_arrived = 0.0

    def _cache_operands(self):
        """The donated KV pool arrays in executable-operand order, as
        the family's ``init_paged_pools`` returned them."""
        return self._pools

    def _set_cache(self, arrs):
        self._pools = tuple(arrs)

    @property
    def _n_cache(self):
        return len(self._pools)

    @property
    def _n_paged(self):
        """How many arrays of the tuple are indexed by page: the first
        ones; the rest are indexed by slot."""
        return len(self._pools) - self._n_slot_state

    def _page_pools(self):
        return self._pools[:self._n_paged]

    def _slot_state(self):
        return self._pools[self._n_paged:]

    def _chunk_eligible(self, req):
        return (self._prefill_chunk is not None
                and len(req.prompt) > self._prefill_chunk)

    def _check_prompt(self, req):
        need = len(req.prompt) + req.max_new_tokens
        need_pages = self._pager.pages_for(need)
        if need_pages > self._num_pages - 1:
            raise ValueError(
                f"request needs {need_pages} KV pages but the pool only "
                f"has {self._num_pages - 1} — raise num_pages or shrink "
                "the request")
        if self._chunk_eligible(req):
            return                  # the chunked path ignores the ladder
        super()._check_prompt(req)

    def _free_slots(self):
        return [i for i in range(self.slots)
                if not self._active[i] and i not in self._chunk_slots]

    def _queued_total(self):
        return len(self._queue) + len(self._inject_queue)

    def _busy(self):
        return (super()._busy() or bool(self._chunk_jobs)
                or bool(self._inject_queue) or bool(self._inflight))

    def _next_admit_seq(self):
        self._admit_seq += 1
        return self._admit_seq

    # ----------------------------------------------------------- admission
    def _admit(self):
        """Wave admission into pages: same same-seq-bucket grouping as
        the slot engine, but each admitted prompt first acquires its
        page table (prefix-cache hits share physical pages).  Page
        exhaustion stops the wave — queued requests simply wait for
        decodes to free pages.  Long prompts divert to the chunked
        path."""
        self._intake_injected()
        self._try_fault_back()
        self._intake_chunked()
        while self._queue and self._free_slots():
            if self._chunk_eligible(self._queue[0]):
                break               # FIFO: the long head waits for intake
            free = self._free_slots()
            group, tables, sbucket, hits_total = [], [], None, 0
            hit_tokens = 0
            exhausted = False
            while (self._queue and len(group) < len(free)
                   and len(group) < self.batch_buckets[-1]):
                nxt = self._queue[0]
                if self._chunk_eligible(nxt):
                    break
                nxt_b = self._seq_bucket(len(nxt.prompt))
                if sbucket is None:
                    sbucket = nxt_b
                elif nxt_b != sbucket:
                    break           # next wave picks it up
                slot = free[len(group)]
                try:
                    with timeline.span("serving.pager.admit",
                                       request_id=nxt.id) as sp:
                        table, hits = self._pager.admit(slot, nxt.prompt)
                        sp.attrs["hits"] = hits
                except self._PagesExhausted:
                    exhausted = True
                    break
                self._queue.popleft()
                nxt.slot = slot
                group.append(nxt)
                tables.append(table)
                hits_total += hits
                # a prompt's last page may be a part of one: the cache
                # supplied no more positions than the prompt has
                hit_tokens += min(hits * self._page_size, len(nxt.prompt))
            if not group:
                break
            self._prefill_group(group, tables, sbucket, hits_total,
                                hit_tokens)
            if exhausted:
                break
        self._g_queue.set(self._queued_total())
        occ = int(self._active.sum())
        self._g_occ.set(occ)
        if not self._warming:
            self._occ_peak = max(self._occ_peak, occ)
            if occ > self._g_occ_peak.value:
                self._g_occ_peak.set(occ)

    def _prefill_group(self, group, tables, sbucket, hits, hit_tokens):
        """Dispatch one wave and admit its requests BY COUNT: slots,
        page tables and lengths are the wave's from here on, and the
        decode step that follows runs them off the first tokens the
        wave scatters into the token vector on the device.  The tokens
        themselves are read back, stamped and committed when the wave
        is retired (:meth:`_read_back`)."""
        jnp = self._jnp
        ps = self._page_size
        bbucket = self._batch_bucket(len(group))
        maxPb = sbucket // ps
        toks = np.zeros((bbucket, sbucket), np.int32)
        lens = np.ones((bbucket,), np.int32)    # pad rows: len 1
        ptab = np.zeros((bbucket, maxPb), np.int32)   # pads -> scratch
        # pad rows: a slot index past the vector, which the scatter drops
        slot_ids = np.full((bbucket,), self.slots, np.int32)
        for r, req in enumerate(group):
            toks[r, :len(req.prompt)] = req.prompt
            lens[r] = len(req.prompt)
            ptab[r, :len(tables[r])] = tables[r]
            slot_ids[r] = req.slot
        fresh = sum(len(t) for t in tables) - hits
        self._inc("prefix_page_hits", hits)
        self._inc("prefix_page_misses", fresh)
        # visible to _abort_inflight, same contract as the base engine
        self._admitting = group
        if tracing.enabled() and not self._warming:
            for req in group:
                tracing.event(
                    "queue_wait", trace_id=req.trace_id,
                    request_id=req.id, batch=bbucket, seq=sbucket,
                    wait_s=round(
                        time.perf_counter() - req.submit_t, 6))
        with timeline.span("serving.prefill_operands"):
            donate = self._donate()
            operands = (self.params, *self._cache_operands(),
                        jnp.asarray(toks), jnp.asarray(lens),
                        jnp.asarray(ptab), self._token_vector(),
                        jnp.asarray(slot_ids))
            fn = self._prefill.get(
                _cc.make_key(bbucket, sbucket, donate=donate,
                             mesh=self._mesh_key()),
                lambda: self._build_prefill(bbucket, sbucket),
                stable_key=self._aot_key("prefill", b=bbucket, s=sbucket),
                example_args=operands, topology=self._topology())
        # what the wave was given (requests, their prompt tokens, the
        # positions the prefix cache supplied) beside what it pays for:
        # the program runs batch x seq rows whatever is in them
        attrs = dict(batch=bbucket, seq=sbucket, requests=len(group),
                     tokens=sum(len(r.prompt) for r in group),
                     rows=bbucket * sbucket, hit_tokens=hit_tokens,
                     request_ids=[r.id for r in group])
        with timeline.span("serving.prefill_wave", **attrs) as sp:
            with timeline.span("serving.prefill_wave.dispatch"):
                out = fn(*operands)
            self._enqueued(True, out, list(enumerate(group)), attrs, sp.id)
            self._inc("prefill_calls")
            self._count_quant_matmuls()
            for r, req in enumerate(group):
                s = req.slot
                self._tables_np[s] = 0
                self._tables_np[s, :len(tables[r])] = tables[r]
                self._lens[s] = len(req.prompt)
                self._todo[s] = req.max_new_tokens - 1
                self._active[s] = True
                self._slot_req[s] = req
                req._admit_seq = self._next_admit_seq()
            self._admitting = []
        if self.capture_logits:
            self._drain("capture_logits")
        elif any(req.prefill_only for req in group):
            # the hand-off extracts the prompt's pages and frees the
            # slot when the first token is committed: now, not a step on
            self._drain("handoff")

    def _replicated(self, x):
        """Pin a program's small output to every device of the mesh, so
        that what one program returns is placed as the host's own copy
        of it is (:meth:`_token_vector`).  No-op without a mesh."""
        if self._mesh is None:
            return x
        return jax_compat.with_sharding_constraint(x, self._mesh, ())

    def _token_vector(self):
        """The ``[slots]`` int32 operand that holds each slot's last
        sampled token: the vector the last program returned while the
        chain holds, else the host's copy (the first dispatch, and the
        first after a drain) — same shape, dtype and placement, so one
        executable takes both."""
        if self._tok_dev is not None:
            return self._tok_dev
        vec = self._jnp.asarray(self._last_tok.copy())   # never aliased
        if self._mesh is not None:
            return self._jax.device_put(
                vec, jax_compat.named_sharding(self._mesh, ()))
        pool = self._pools[0]
        if pool.committed:      # as a program's outputs then are
            return self._jax.device_put(vec, pool.sharding)
        return vec

    def _scatter_first(self, first_tok, chain):
        """``(vector', )``: the wave's first tokens scattered into the
        token vector at the wave's slots (pad rows carry an index past
        the vector, which is dropped).  ``()`` for a caller that lowers
        the wave without the chain (benchmark/sizing reads its memory)."""
        if len(chain) == 0:
            return ()
        vec, slot_ids = chain
        return (self._replicated(
            vec.at[slot_ids].set(first_tok, mode="drop")),)

    def _build_prefill(self, b, s):
        """Paged prefill executable: the family's causal forward over
        the padded prompts, writing the DONATED pool through the page
        tables (the family's ``prefill_paged``; one that keeps state
        per slot is also handed the rows' slot ids, FAMILY_INTERFACE),
        then the first token of each row — as an output the host reads
        back (a family's per-wave counts behind them), and scattered
        into the token vector the next program takes (the program's
        last output)."""
        jax, jnp = self._jax, self._jnp
        cfg = self.cfg
        ps = self._page_size
        cap = self.capture_logits

        if self._pp > 1:
            # stage-partitioned wave: one shard_map over the ('pp','tp')
            # mesh runs the 1F1B fill, each stage scattering its OWN
            # layer range's pages (models/gpt_pp.py).  Same operand
            # order and outputs as the GSPMD path below.
            from ..models import gpt_pp
            pre = gpt_pp.make_prefill_step(
                cfg, self._mesh, self._param_specs, s=s, b=b,
                page_size=ps)

            def prefill_pp(params, cache_k, cache_v, tokens, lens, ptab,
                           *chain):
                ck, cv, first_tok, last = pre(
                    params, cache_k, cache_v, tokens, lens, ptab)
                out_cache = self._constrain_cache((ck, cv))
                return (*out_cache, first_tok, *((last,) if cap else ()),
                        *self._scatter_first(first_tok, chain))

            donate = ((1, 2) if _donation_enabled() else ())
            return jax.jit(prefill_pp, donate_argnums=donate)

        n = self._n_cache
        family = self._family
        per_slot = self._n_slot_state > 0

        def prefill(params, *args):
            tokens, lens, ptab, *chain = args[n:]
            if per_slot and not chain:
                raise ValueError(
                    f"{family.__name__} keeps state per slot: its wave "
                    "cannot be lowered without the rows' slot ids")
            last, out_cache, *extra = family.prefill_paged(
                params, cfg, args[:n], tokens, lens, ptab,
                **({"slots": chain[1]} if per_slot else {}))
            out_cache = self._constrain_cache(out_cache)
            with jax.named_scope("head_sample"):
                first_tok = back = jnp.argmax(last, -1).astype(jnp.int32)
                chained = self._scatter_first(first_tok, chain)
                if extra:
                    # what the family counts a wave rides the first
                    # tokens' readback, as a decode step's counts do
                    back = jnp.concatenate(
                        [first_tok, extra[0].reshape(-1).astype(jnp.int32)])
            return (*out_cache, back, *((last,) if cap else ()), *chained)

        donate = tuple(range(1, 1 + n)) if _donation_enabled() else ()
        return self._jax.jit(prefill, donate_argnums=donate)

    # ------------------------------------------------------ chunked prefill
    def _intake_chunked(self):
        """Claim a slot + the full prompt's page table for long prompts
        at the queue head; the chunks themselves run one per engine
        iteration in :meth:`_advance_chunks`.  Fresh pages are NOT
        prefix-registered until their content lands (deferred
        registration) — a concurrent identical prompt must never share
        an unwritten page."""
        if self._prefill_chunk is None:
            return
        while self._queue and self._chunk_eligible(self._queue[0]):
            free = self._free_slots()
            if not free:
                return
            req = self._queue[0]
            slot = free[0]
            try:
                table, hits = self._pager.admit(slot, req.prompt,
                                                defer_register=True)
            except self._PagesExhausted:
                return              # decodes will free pages; retry later
            self._queue.popleft()
            req.slot = slot
            req._chunk_pos = 0
            req._chunk_time = 0.0
            req._admit_seq = self._next_admit_seq()
            self._chunk_slots.add(slot)
            self._chunk_jobs.append(req)
            self._slot_req[slot] = req
            self._tables_np[slot] = 0
            self._tables_np[slot, :len(table)] = table
            self._inc("prefix_page_hits", hits)
            self._inc("prefix_page_misses", len(table) - hits)

    def _advance_chunks(self):
        """Run ONE prefill chunk of the oldest chunked admission — the
        interleaving contract: in-flight decodes get an iteration
        between every pair of chunks instead of stalling behind the
        whole long prompt."""
        if not self._chunk_jobs:
            return
        jnp = self._jnp
        req = self._chunk_jobs[0]
        C = self._prefill_chunk
        n = len(req.prompt)
        pos = req._chunk_pos
        take = min(C, n - pos)
        if pos + take >= n:
            # the last chunk's token is read here and the slot joins the
            # decode pool from the host's copy of the token vector
            self._drain("chunk_done")
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = req.prompt[pos:pos + take]
        s = req.slot
        operands = (self.params, *self._cache_operands(),
                    jnp.asarray(toks), jnp.asarray(self._tables_np[s]),
                    np.int32(pos), np.int32(take),
                    *((np.int32(s),) if self._n_slot_state else ()))
        if self._chunk_jit is None:
            donate = self._donate()
            self._chunk_jit = self._chunk_site.get(
                _cc.make_key("chunk", C, donate=donate,
                             mesh=self._mesh_key()),
                lambda: self._build_chunk(C),
                stable_key=self._aot_key("chunk", c=C),
                example_args=operands, topology=self._topology())
            self._inc("prefill_compiles")
        t0 = time.perf_counter()
        # what the chunk was given beside what its program runs: 1 x C
        # rows whatever the prompt has left
        with timeline.span("serving.prefill_chunk", pos=pos, tokens=take,
                           rows=C):
            out = self._chunk_jit(*operands)
        self._set_cache(out[:self._n_cache])
        tok = out[self._n_cache]
        # ptl: disable-next=PTL004 -- capture_logits debug mode readback
        row_np = (np.asarray(out[self._n_cache + 1])
                  if self.capture_logits else None)
        self._inc("prefill_chunks")
        self._count_quant_matmuls()
        if tracing.enabled() and not self._warming:
            tracing.event("prefill_chunk", trace_id=req.trace_id,
                          request_id=req.id, pos=pos, take=take,
                          chunk_s=round(time.perf_counter() - t0, 6),
                          engine=self._engine_id)
        req._chunk_pos = pos + take
        # the HOST's wall time around the chunk calls, summed over the
        # admission and observed once at its end under a name of its own
        # (``serving.prefill_chunked_s``): ``serving.prefill_s`` holds
        # one kind of interval, the device's for a wave (_read_back)
        req._chunk_time += time.perf_counter() - t0
        self._pager.register_prompt(s, req._chunk_pos)
        if req._chunk_pos < n:
            return                  # decode runs before the next chunk
        # final chunk: the prompt is in — the sampled token admits the
        # request into the decode pool like a one-shot prefill would
        self._chunk_jobs.popleft()
        self._chunk_slots.discard(s)
        self._lens[s] = n
        self._todo[s] = req.max_new_tokens - 1
        self._active[s] = True
        self._append_token(req, int(tok), row_np)
        self._last_tok[s] = int(tok)
        self._inc("requests_admitted")
        self._memo_first_token(req)
        if not self._warming:
            self._h_chunked.observe(req._chunk_time)
        if _faults.active() and not self._warming:
            _faults.replica_kill_check(
                request=self._counts["requests_admitted"])
        self._maybe_finish_prefill_only(req)

    def _build_chunk(self, C):
        """ONE executable serves every chunk of every long prompt: the
        absolute position offset and the chunk's true token count are
        traced scalars, so chunk index never changes the signature.
        The family's ``chunk_paged`` is the program (GPT's int8 pool:
        dequantized gather view in, quantized chunk-only scatter
        out); one that keeps state per slot is handed the slot and the
        chunk's true length, and returns that row's logits alone
        (FAMILY_INTERFACE)."""
        jax, jnp = self._jax, self._jnp
        cfg = self.cfg
        cap = self.capture_logits
        n = self._n_cache
        family = self._family

        per_slot = self._n_slot_state > 0

        def chunk(params, *args):
            toks, ptab_row, offset, tlen, *slot = args[n:]
            if per_slot:
                last, cache = family.chunk_paged(
                    params, cfg, args[:n], toks, ptab_row, offset,
                    slot=slot[0], take=tlen)
            else:
                logits, cache = family.chunk_paged(
                    params, cfg, args[:n], toks, ptab_row, offset)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], tlen - 1, 0, keepdims=False)        # [V]
            tok = jnp.argmax(last, -1).astype(jnp.int32)
            cache = self._constrain_cache(cache)
            if cap:
                return (*cache, tok, last)
            return (*cache, tok)

        donate = (tuple(range(1, 1 + self._n_cache))
                  if _donation_enabled() else ())
        return jax.jit(chunk, donate_argnums=donate)

    # ----------------------------------------------------- page lifecycle
    def _finish(self, req, reason):
        s = req.slot
        super()._finish(req, reason)
        if s is not None:
            self._pager.release(s)
            self._tables_np[s] = 0

    def _get_copy_jit(self):
        if self._copy_jit is None:
            self._copy_jit = self._copy_site.get(
                _cc.make_key("copy", donate=self._donate(0),
                             mesh=self._mesh_key()),
                self._build_copy,
                stable_key=self._aot_key("copy"),
                example_args=(*self._cache_operands(),
                              np.int32(0), np.int32(0)),
                topology=self._topology())
        return self._copy_jit

    def _copy_page(self, src, dst):
        """Device-side copy-on-write: duplicate page ``src`` into the
        freshly-owned ``dst`` before the diverging write lands.  One
        jitted donated executable, compiled once (warmup primes it).
        On the int8 pool the page's scale rows travel WITH its bytes —
        an int8 page without its scales is garbage."""
        self._set_cache(self._get_copy_jit()(
            *self._cache_operands(), np.int32(src), np.int32(dst)))
        self._inc("cow_copies")

    def _build_copy(self):
        jax = self._jax

        pages = self._n_paged

        def cp(*args):
            arrs, (src, dst) = args[:-2], args[-2:]
            # a page's bytes move; what a slot owns stays where it is
            return self._constrain_cache(
                tuple(a.at[:, dst].set(a[:, src]) for a in arrs[:pages])
                + arrs[pages:])

        donate = (tuple(range(self._n_cache))
                  if _donation_enabled() else ())
        return jax.jit(cp, donate_argnums=donate)

    # ------------------------------------------- KV handoff (ISSUE 15)
    #
    # Prefill/decode disaggregation ships a finished prompt's KV pages
    # from a prefill-role engine to a decode-role engine (DistServe/
    # Splitwise): the prefill engine admits a ``prefill_only`` request
    # through the NORMAL wave/chunked paths, then — instead of decoding
    # — extracts its pages to host bytes, finishes it with reason
    # "prefill_done", and releases the pages (prompt pages retire to
    # the prefix-reclaim LRU, so a repeated system prompt prefills
    # free).  The decode engine re-acquires a page table for the SAME
    # prompt (prefix hits share physical pages — the shipped bytes are
    # deterministic, so rewriting a shared page writes what it already
    # holds) and scatters the payload in with ONE injection executable.
    # Both directions are one fixed-shape executable each (pages padded
    # to the per-slot table width), so the zero-steady-state-compiles
    # invariant survives disaggregation.

    def _build_extract(self):
        jax = self._jax
        n = self._n_cache

        def extract(*args):
            cache, pages = args[:n], args[-1]
            return tuple(c[:, pages] for c in cache)

        return jax.jit(extract)     # read-only: the pool is NOT donated

    def _extract_pages_row(self, pages_row):
        """Dispatch the fixed-width page-gather executable over an
        explicit full-width ``pages_row`` (pads aimed at scratch) and
        return the still-on-device output arrays — the shared primitive
        under the disaggregation handoff AND the host-tier spill
        capture, so both ride ONE executable and neither ever compiles
        in steady state."""
        jnp = self._jnp
        operands = (*self._cache_operands(), jnp.asarray(pages_row))
        if self._extract_jit is None:
            self._extract_jit = self._extract_site.get(
                _cc.make_key("extract", mesh=self._mesh_key()),
                self._build_extract,
                stable_key=self._aot_key("extract"),
                example_args=operands, topology=self._topology())
            self._inc("handoff_compiles")
        return self._extract_jit(*operands)

    def _extract_slot_kv(self, slot, n_pages):
        """The slot's first ``n_pages`` pages of every pool operand as
        host arrays (k, v — plus scales on the int8 pool), via one
        fixed-width gather executable."""
        with timeline.span("serving.kv_extract", pages=int(n_pages)):
            out = self._extract_pages_row(self._tables_np[slot])
        self._inc("kv_extracts")
        # the handoff readback: these pages LEAVE the replica as wire
        # bytes by design — the disaggregation shipping path, not a
        # hot-loop leak
        # ptl: disable-next=PTL004 -- KV handoff readback (pages ship out)
        return [np.asarray(a)[:, :int(n_pages)] for a in out]

    def _maybe_finish_prefill_only(self, req):
        """Finish a ``prefill_only`` admission the moment its prompt is
        in: pages extracted onto ``req.kv_payload``, request finished
        with reason "prefill_done" (slot + pages released).  A request
        that finished NATURALLY during admission (eos on the first
        token, max_new_tokens == 1) ships no pages — its completion is
        already final."""
        if not req.prefill_only or req.done or self._warming:
            return
        s = req.slot
        n_pages = len(self._pager.tables[s])
        req.kv_payload = self._extract_slot_kv(s, n_pages)
        kv_bytes = sum(int(a.nbytes) for a in req.kv_payload)
        self._inc("kv_handoff_bytes", kv_bytes)
        if tracing.enabled():
            tracing.event("extract", trace_id=req.trace_id,
                          request_id=req.id, pages=n_pages,
                          kv_bytes=kv_bytes, engine=self._engine_id)
        self._finish(req, "prefill_done")

    def submit_prefilled(self, req, first_token, kv_arrays):
        """Admit a request whose prompt KV was prefilled on ANOTHER
        engine (the disaggregation handoff).  ``req`` is a prepared
        :class:`Request`; ``kv_arrays`` is one host array per pool
        operand, shaped ``[L, n_pages, page_size, ...]`` for the
        prompt's pages (what the prefill side's ``kv_payload`` holds);
        ``first_token`` is the prefill's sampled first token.  Queued
        on the injection queue — the next :meth:`step` acquires pages
        and scatters the payload in.  Identical params make the decode
        byte-stream token-exact with a never-disaggregated run."""
        if not isinstance(req, Request):
            raise TypeError("submit_prefilled wants a prepared Request")
        if not self._handoff:
            # symmetric with submit()'s prefill_only guard: without
            # kv_handoff=True the inject executable was never primed,
            # so the first injection would compile in live traffic
            raise ValueError(
                "handed-off admission needs "
                "PagedServingEngine(kv_handoff=True) — this engine's "
                "warmup never primed the injection executable")
        if self.capture_logits:
            raise ValueError(
                "capture_logits engines cannot admit handed-off "
                "requests — the first token's logits row stayed on the "
                "prefill replica, so the per-token capture would be "
                "misaligned from its first entry")
        need_pos = len(req.prompt) + req.max_new_tokens
        if need_pos > self.max_len:
            # same admission bound as submit(): past max_len the
            # fixed-width page table overflows and positions reuse the
            # last positional embedding — reject up front, not mid-step
            raise ValueError(
                f"request needs {need_pos} cache positions "
                f"(prompt {len(req.prompt)} + {req.max_new_tokens} new) "
                f"> max_len {self.max_len}")
        need = self._pager.pages_for(need_pos)
        if need > self._num_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self._num_pages - 1}")
        ops = self._cache_operands()
        if len(kv_arrays) != len(ops):
            raise ValueError(
                f"kv payload has {len(kv_arrays)} arrays; this pool "
                f"has {len(ops)} operands (fp: k,v; int8: k,k_scale,"
                "v,v_scale)")
        n_pages = self._pager.pages_for(len(req.prompt))
        arrays = []
        for a, pool in zip(kv_arrays, ops):
            a = np.asarray(a)
            want = (pool.shape[0], n_pages) + tuple(pool.shape[2:])
            if tuple(a.shape) != want or a.dtype != np.dtype(pool.dtype):
                raise ValueError(
                    f"kv payload shape/dtype {a.shape}/{a.dtype} does "
                    f"not match the pool's page layout {want}/"
                    f"{pool.dtype} — mismatched engine configs can "
                    "never hand off")
            arrays.append(a)
        if self._queued_total() >= self.max_queue:
            self._inc("queue_rejects")
            raise ServingQueueFull(
                f"queue depth {self._queued_total()} at max_queue "
                f"{self.max_queue}")
        req._inject = arrays
        req._inject_tok = int(first_token)
        self._inject_queue.append(req)
        self._g_queue.set(self._queued_total())
        return req

    def _build_inject(self):
        jax = self._jax
        n = self._n_cache

        def inject(*args):
            cache, payload, pages = args[:n], args[n:2 * n], args[-1]
            return self._constrain_cache(tuple(
                c.at[:, pages].set(p) for c, p in zip(cache, payload)))

        donate = (tuple(range(n)) if _donation_enabled() else ())
        return jax.jit(inject, donate_argnums=donate)

    def _inject_call(self, pages_row, payload):
        """One injection dispatch: scatter ``payload`` (already padded
        to the table width, pad rows aimed at the scratch page) into
        the donated pool at ``pages_row``."""
        jnp = self._jnp
        operands = (*self._cache_operands(),
                    *(jnp.asarray(p) for p in payload),
                    jnp.asarray(pages_row))
        if self._inject_jit is None:
            self._inject_jit = self._inject_site.get(
                _cc.make_key("inject", donate=self._donate(0),
                             mesh=self._mesh_key()),
                self._build_inject,
                stable_key=self._aot_key("inject"),
                example_args=operands, topology=self._topology())
            self._inc("handoff_compiles")
        with timeline.span("serving.kv_inject"):
            self._set_cache(self._inject_jit(*operands))

    def _pad_payload(self, arrays, n_pages):
        maxP = self._pages_per_slot
        out = []
        for a in arrays:
            pad = np.zeros((a.shape[0], maxP) + tuple(a.shape[2:]),
                           a.dtype)
            pad[:, :n_pages] = a
            out.append(pad)
        return out

    def _intake_injected(self):
        """Admit shipped-KV requests from the injection queue: acquire
        a page table for the prompt (prefix hits share pages — the
        injection rewrites bytes identical to what a shared page
        already holds), scatter the payload in, and activate the slot
        with the prefill's first token already committed.  Page
        exhaustion leaves the queue intact — decodes free pages."""
        while self._inject_queue:
            free = self._free_slots()
            if not free:
                return
            self._drain("inject")   # the slot is written from the host
            req = self._inject_queue[0]
            slot = free[0]
            try:
                table, hits = self._pager.admit(slot, req.prompt)
            except self._PagesExhausted:
                return
            self._inject_queue.popleft()
            req.slot = slot
            n_pages = len(table)
            pages_row = np.zeros((self._pages_per_slot,), np.int32)
            pages_row[:n_pages] = table
            self._inject_call(pages_row,
                              self._pad_payload(req._inject, n_pages))
            self._inc("prefix_page_hits", hits)
            self._inc("prefix_page_misses", n_pages - hits)
            self._inc("kv_injects")
            if tracing.enabled() and not self._warming:
                tracing.event("inject", trace_id=req.trace_id,
                              request_id=req.id, pages=n_pages,
                              prefix_hits=hits, engine=self._engine_id)
            self._tables_np[slot] = pages_row
            self._lens[slot] = len(req.prompt)
            self._todo[slot] = req.max_new_tokens - 1
            self._active[slot] = True
            self._slot_req[slot] = req
            req._admit_seq = self._next_admit_seq()
            self._append_token(req, req._inject_tok, None)
            self._last_tok[slot] = req._inject_tok
            self._inc("requests_admitted")
            self._g_queue.set(self._queued_total())
            self._memo_first_token(req)
            if _faults.active() and not self._warming:
                _faults.replica_kill_check(
                    request=self._counts["requests_admitted"])

    # ------------------------------------------- host page tier (ISSUE 17)
    #
    # The tier turns device evictions into demotions: the pager's
    # reclaim-LRU eviction hook captures the page's bytes through the
    # SAME fixed-width extract executable the disaggregation handoff
    # uses (one synthetic row, the pid at position 0), and the post-step
    # drain moves them to the pinned-host LRU with a content-hash stamp.
    # A later prompt whose page chain is fully covered by device hits
    # plus hash-verified host entries — and whose first token is
    # memoized (greedy decoding is deterministic, so the first token is
    # a pure function of params and prompt) — admits through the
    # donated inject executable WITHOUT re-prefilling.  Every moving
    # part reuses an already-warm executable, so the zero-steady-state-
    # compiles invariant survives the tier.

    def _on_page_evicted(self, pid, key):
        """Pager eviction hook: dispatch the page-gather NOW, while the
        pid's bytes are still valid (the caller reuses the pid right
        after), but keep the result on device — the host readback
        defers to the post-step drain so a slow host copy
        (``spill_stall``) never blocks the decode dispatch."""
        if self._warming or self._host_tier is None:
            return
        row = np.zeros((self._pages_per_slot,), np.int32)  # pads->scratch
        row[0] = pid
        self._spill_pending.append((key, self._extract_pages_row(row)))

    def _drain_spills(self):
        """Deferred half of the spill: host readback, content-hash
        stamp, LRU insert.  Runs from ``step()``'s finally — strictly
        after the decode dispatch of the step that evicted."""
        if not self._spill_pending or self._host_tier is None:
            self._spill_pending.clear()
            return
        while self._spill_pending:
            key, arrays = self._spill_pending.popleft()
            if _faults.active() and not self._warming:
                stall = _faults.spill_stall()
                if stall is not None:
                    time.sleep(stall)
            # the page moves DOWN a tier by design — a demotion copy,
            # not a hot-loop leak
            # ptl: disable-next=PTL004 -- host-tier spill readback
            host = [np.asarray(a)[:, :1].copy() for a in arrays]
            self._host_tier.put(key, host)
            if (_faults.active() and not self._warming
                    and _faults.host_tier_corrupt()):
                self._host_tier.corrupt(key)
            self._inc("pages_spilled")
            self._inc("spill_bytes", sum(int(h.nbytes) for h in host))
        self._g_host_tier.set(self._host_tier.bytes)

    def step(self):
        """Base step plus the spill drain.  The drain lives HERE (not
        ``_step_inner``, which the speculative engine overrides
        wholesale) so every paged variant demotes evicted pages."""
        try:
            return super().step()
        finally:
            if self._spill_pending:
                self._drain_spills()

    def _memo_first_token(self, req):
        """Record the prompt's greedy first token under its chain-tail
        page key (already salted by quant/kv-dtype config) — the
        admission ticket for a later no-prefill fault-back."""
        if self._host_tier is None or self._warming or not req.tokens:
            return
        keys = self._pager._prompt_keys(req.prompt)
        if not keys:
            return
        memo = self._first_tok_memo
        memo[keys[-1]] = int(req.tokens[0])
        memo.move_to_end(keys[-1])
        while len(memo) > 8192:
            memo.popitem(last=False)

    def _try_fault_back(self):
        """Head-of-queue fault-back admission: when the head prompt's
        FULL page chain is covered by device prefix hits plus
        hash-verified host-tier entries, and its first token is
        memoized, admit through the inject executable instead of
        re-prefilling.  Anything short of full verified coverage falls
        through to the normal prefill paths (head-only keeps FIFO
        order; a corrupt host entry is dropped and the prompt simply
        re-prefills — bad KV is never served)."""
        if (self._host_tier is None or not self._prefix_cache_on
                or self.capture_logits):
            return
        while self._queue:
            free = self._free_slots()
            if not free:
                return
            req = self._queue[0]
            keys = self._pager._prompt_keys(req.prompt)
            if not keys or keys[-1] not in self._first_tok_memo:
                return
            fetched = {}
            covered = True
            for key in keys:
                if self._pager.cached_page(key) is not None:
                    continue
                got = self._host_tier.fetch(key)
                if got == "corrupt":
                    self._inc("fault_back_rejects")
                    covered = False
                    break
                if got is None:
                    covered = False
                    break
                fetched[key] = got
            if not covered or not fetched:
                return      # device-only hits: the prefill wave wins
            self._drain("fault_back")   # the slot is written from the host
            slot = free[0]
            try:
                table, hit_flags = self._pager.admit_pinned(
                    slot, req.prompt)
            except self._PagesExhausted:
                return
            # inject ONLY the missing pages (device hits already hold
            # their bytes); fresh pids pack the row head, pads scratch
            miss = [(i, k) for i, (k, h)
                    in enumerate(zip(keys, hit_flags)) if not h]
            pages_row = np.zeros((self._pages_per_slot,), np.int32)
            cols = None
            for j, (i, key) in enumerate(miss):
                pages_row[j] = table[i]
                arrays, age = fetched[key]
                self._h_reclaim_age.observe(age)
                if cols is None:
                    cols = [[a] for a in arrays]
                else:
                    for lst, a in zip(cols, arrays):
                        lst.append(a)
            payload = [np.concatenate(lst, axis=1) for lst in cols]
            self._inject_call(pages_row,
                              self._pad_payload(payload, len(miss)))
            self._queue.popleft()
            req.slot = slot
            n_pages = len(table)
            self._tables_np[slot] = 0
            self._tables_np[slot, :n_pages] = table
            self._lens[slot] = len(req.prompt)
            self._todo[slot] = req.max_new_tokens - 1
            self._active[slot] = True
            self._slot_req[slot] = req
            req._admit_seq = self._next_admit_seq()
            tok = int(self._first_tok_memo[keys[-1]])
            self._append_token(req, tok, None)
            self._last_tok[slot] = tok
            # the whole chain served without prefill: every page is a
            # prefix hit from the fleet's point of view
            self._inc("prefix_page_hits", n_pages)
            self._inc("pages_faulted_back", len(miss))
            self._inc("fault_backs")
            self._inc("kv_injects")
            self._inc("requests_admitted")
            self._g_queue.set(self._queued_total())
            if not self._warming and timeline.telemetry_dir():
                timeline.emit({"event": "kv_fault_back",
                               "request_id": str(req.id),
                               "pages": len(miss),
                               "device_hits": n_pages - len(miss)})
            if not self._warming:
                tracing.event("fault_back", trace_id=req.trace_id,
                              request_id=req.id, pages=len(miss),
                              device_hits=n_pages - len(miss),
                              engine=self._engine_id)
            if _faults.active() and not self._warming:
                _faults.replica_kill_check(
                    request=self._counts["requests_admitted"])
            self._maybe_finish_prefill_only(req)

    def _newest_victim(self):
        """The most recently admitted in-flight request (decode-active
        or mid-chunked-prefill) — the preemption policy's target."""
        cands = [r for r in self._slot_req if r is not None and not r.done]
        if not cands:
            return None
        return max(cands, key=lambda r: getattr(r, "_admit_seq", -1))

    def _preempt(self, req, why):
        """Page-exhaustion eviction: free the victim's slot and pages,
        scrub it back to its prompt, and put it at the queue head for
        re-admission once pages free up.  NAMED (telemetry event,
        ``preemptions`` counter, ``Request.preemptions``) — exhaustion
        is never a silent stall or loss.  Every caller has committed
        what was in flight: the victim restarts from its prompt with
        every token the client was already sent accounted for."""
        s = req.slot
        if s is not None:
            self._pager.release(s)
            self._tables_np[s] = 0
            self._active[s] = False
            self._lens[s] = 0
            self._slot_req[s] = None
            if s in self._chunk_slots:
                self._chunk_slots.discard(s)
                try:
                    self._chunk_jobs.remove(req)
                except ValueError:
                    pass
        req.reset_for_retry()
        req.preemptions += 1
        if req._inject is not None:
            # a preempted INJECTED request re-injects its shipped pages
            # (re-prefilling locally would be correct but would drag
            # prefill work onto a decode-role replica)
            self._inject_queue.appendleft(req)
        else:
            self._queue.appendleft(req)
        self._inc("preemptions")
        self._g_queue.set(self._queued_total())
        if not self._warming and timeline.telemetry_dir():
            timeline.emit({"event": "page_exhaustion",
                           "request_id": str(req.id),
                           "action": "preempted", "reason": why})
        if not self._warming:
            tracing.event("preemption", trace_id=req.trace_id,
                          request_id=req.id, reason=str(why)[:160],
                          preemptions=req.preemptions,
                          engine=self._engine_id)

    def _ensure_decode_pages(self):
        """Give every slot this step runs a writable position for its
        token: a fresh tail page on a page boundary, a COW copy when the
        tail is shared.  A slot runs while its request may still be
        given a position (``_todo``: one that ends by length is known
        by count, before its last token is read).  On exhaustion, first
        commit what is in flight — a request that finished there frees
        its pages — then preempt the newest request, and retry
        (``ensure_append`` is idempotent, so re-walking already-ensured
        slots is safe): progress is guaranteed because a lone request
        always fits (submit enforces it).  Returns the mask of the
        slots to run and their write coordinates."""
        ps = self._page_size
        while True:
            run = self._active & (self._todo > 0)
            wpages = np.zeros((self.slots,), np.int32)  # idle -> scratch
            woffs = np.zeros((self.slots,), np.int32)
            try:
                for s in np.flatnonzero(run):
                    pos = int(self._lens[s])
                    pid, off, cow_src = self._pager.ensure_append(s, pos)
                    if cow_src is not None:
                        self._copy_page(cow_src, pid)
                    self._tables_np[s, pos // ps] = pid
                    wpages[s] = pid
                    woffs[s] = off
                return run, wpages, woffs
            except self._PagesExhausted as e:
                if self._inflight:
                    self._drain("page_exhaustion")
                    continue
                victim = self._newest_victim()
                if victim is None:
                    raise
                self._preempt(victim, str(e))

    # ------------------------------------------------ the step in flight
    def _enqueued(self, wave, out, rows, attrs, span):
        """Take over what a program just enqueued returned: the pool,
        the token vector for the next program (its last output), and
        the record of the sampled tokens the host has yet to read,
        which keeps the id of the span it was dispatched under."""
        n = self._n_cache
        self._set_cache(out[:n])
        self._tok_dev = out[-1]
        self._inflight.append(_Dispatched(
            wave, self._step_idx, out[n],
            out[n + 1] if self.capture_logits else None, rows, attrs,
            span))

    def _retire(self, before=None):
        """Read back and commit the programs in flight, oldest first —
        all of them, or those enqueued by a ``step()`` earlier than
        ``before``."""
        while self._inflight and (before is None
                                  or self._inflight[0].step < before):
            self._read_back(self._inflight.popleft())

    def _drain(self, reason):
        """Make the host's view whole: read back and commit everything
        in flight, and start the next dispatch's token vector from the
        host's copy.  Called by whatever needs host-visible tokens or
        rewrites a slot from the host; ``stats()["drains"]`` counts, by
        ``reason``, the calls that found a program in flight."""
        self._tok_dev = None
        if not self._inflight:
            return
        self._retire()
        if not self._warming:
            self._drains[reason] += 1

    def _read_back(self, rec):
        """Block on one program's sampled tokens, stamp and commit
        them.  The histograms (``serving.prefill_s`` for a wave,
        ``serving.decode_step_s``) observe the time the device had the
        program at the head of its queue: from the later of its enqueue
        returning and the previous program's tokens arriving, to its
        own tokens' arrival.  The span carries the same interval as
        ``device_s`` and names the span the program was dispatched
        under (``dispatch_span``), so over any stretch of steps the
        ``device_s`` of the wave spans sum to what ``serving.prefill_s``
        observed and those of the decode spans to
        ``serving.decode_step_s``'s."""
        name = "serving.prefill_wave" if rec.wave else "serving.decode"
        with timeline.span(name, dispatch_span=rec.span,
                           **rec.attrs) as sp:
            with timeline.span(name + ".readback"):
                # ptl: disable-next=PTL004 -- capture_logits debug readback
                logits_np = (None if rec.logits is None
                             else np.asarray(rec.logits))
                # THE designed device->host sync of the loop (tokens must
                # reach clients), one step behind the dispatch in steady
                # state: the copy started when the program was enqueued
                # ptl: disable-next=PTL004 -- lagged sampled-token readback
                toks_np = np.asarray(rec.toks)
                arrived = time.perf_counter()
            dt = arrived - max(rec.t_enq, self._t_arrived)
            self._t_arrived = arrived
            # the ring's alone: a profiler's trace has the device's line
            sp.attrs["device_s"] = dt
            if not self._warming:
                (self._h_prefill if rec.wave else self._h_decode
                 ).observe(dt)
                if rec.wave:
                    self._count_wave(rec.attrs, dt)
            if rec.wave:
                self._commit_wave(rec, toks_np, logits_np)
            else:
                self._commit_decode(rec, toks_np, logits_np, dt)

    def _count_wave(self, attrs, dt):
        """The operator's view of prefill, summed where a wave is
        committed (a preempted request's second prefill is paid again
        and counts again; :meth:`_read_back` leaves warm-up's waves
        out, as it does for the histograms):
        ``stats()["prefill_by_bucket"]`` and the two totals beside it,
        the prompt tokens the waves were given and the rows (batch x
        seq, padding included) their programs ran."""
        self._inc("prefill_tokens", attrs["tokens"])
        self._inc("prefill_padded_rows", attrs["rows"])
        row = self._prefill_by_bucket.setdefault(
            f"{attrs['batch']}x{attrs['seq']}",
            dict(waves=0, requests=0, tokens=0, rows=0, device_s=0.0))
        row["waves"] += 1
        row["requests"] += attrs["requests"]
        row["tokens"] += attrs["tokens"]
        row["rows"] += attrs["rows"]
        row["device_s"] += dt

    def _commit_wave(self, rec, first_np, logits_np):
        if first_np.shape[0] > rec.attrs["batch"] and not self._warming:
            # the family's per-wave counts, behind the first tokens
            # (traffic counters: a warm-up wave counts nothing, here as
            # in _count_wave, so the two sides' rows can be compared)
            extra = self._family.prefill_extra_stats(
                self.cfg, first_np[rec.attrs["batch"]:])
            for k, v in extra.items():
                self._inc(k, v)
        for r, req in rec.rows:
            tok = int(first_np[r])
            self._append_token(req, tok, None if logits_np is None
                               else logits_np[r])
            self._last_tok[req.slot] = tok
            self._inc("requests_admitted")
            self._memo_first_token(req)
            if _faults.active() and not self._warming:
                _faults.replica_kill_check(
                    request=self._counts["requests_admitted"])
            self._maybe_finish_prefill_only(req)

    def _commit_decode(self, rec, nxt_np, logits_np, dt):
        if nxt_np.shape[0] > self.slots and not self._warming:
            # the family's per-step counts, behind the tokens
            extra = self._family.decode_extra_stats(
                self.cfg, nxt_np[self.slots:])
            for k, v in extra.items():
                self._inc(k, v)
        finished = []
        with timeline.span("serving.decode.commit"):
            for s, req in rec.rows:
                if req.done:
                    # ended by eos_token a step ago, after this position
                    # was dispatched: its token is dropped
                    continue
                tok = int(nxt_np[s])
                self._append_token(req, tok, None if logits_np is None
                                   else logits_np[s])
                self._last_tok[s] = tok
                if req.done:
                    finished.append(req)
        self._g_occ.set(int(self._active.sum()))
        if not self._warming and timeline.telemetry_dir():
            timeline.emit({"event": "serving_step",
                           "active": int(self._active.sum()),
                           "queue": len(self._queue),
                           "decode_s": round(dt, 6),
                           "finished": len(finished),
                           "pages_in_use": self._pager.pages_in_use(),
                           "pages_spilled":
                               self._counts.get("pages_spilled", 0),
                           "pages_faulted_back":
                               self._counts.get("pages_faulted_back", 0),
                           "chain_digests":
                               self._pager.stats()["chain_digest_count"],
                           # per-process total order + emitter (ISSUE 19)
                           "seq": tracing.seq(),
                           "engine": self._engine_id,
                           "replica": self._replica,
                           "finished_ids": [str(r.id) for r in finished]})
        if tracing.enabled() and not self._warming:
            for r in finished:
                tracing.event("decode_iter", trace_id=r.trace_id,
                              request_id=r.id, iters=len(r.tokens),
                              decode_s=round(dt, 6),
                              engine=self._engine_id)

    def _abort_inflight(self, err):
        """Base abort; the step in flight is DISCARDED unread (the
        rebuild drops it with the pool it wrote: its requests are among
        the victims and restart from their prompts)."""
        if self._inflight and not self._warming:
            self._drains["abort"] += 1
        return super()._abort_inflight(err)

    # ------------------------------------------------------------- driving
    def _step_inner(self):
        """Dispatch this step — admission's waves, then the decode —
        and only then read back and commit the step before it, which
        the device computed meanwhile."""
        with timeline.span("serving.admit"):
            self._admit()
        self._advance_chunks()
        if _faults.active() and not self._warming:
            # an injected fault is aimed at a step by its number, and
            # what completed before it stays completed
            self._drain("fault_injection")
            if self._active.any():
                if _faults.page_exhaustion_check(
                        step=self._counts["decode_steps"] + 1):
                    victim = self._newest_victim()
                    if victim is not None:
                        self._preempt(victim, "injected page_exhaustion")
                _faults.engine_step_error(
                    self._counts["decode_steps"] + 1)
                _faults.replica_kill_check(
                    step=self._counts["decode_steps"] + 1)
        with timeline.span("serving.pager.ensure"):
            run, wpages, woffs = self._ensure_decode_pages()
        if not run.any():
            # nothing to give the device: what it still holds is all
            # that is left to deliver
            self._drain("idle")
            return
        jnp = self._jnp
        with timeline.span("serving.decode_operands"):
            # the host rewrites its page tables while this program is
            # still queued, and ``jnp.asarray`` may alias a numpy
            # array's memory (it does on the CPU): the program gets a
            # copy that nobody writes again
            operands = (self.params, *self._cache_operands(),
                        jnp.asarray(self._tables_np.copy()),
                        jnp.asarray(wpages), jnp.asarray(woffs),
                        jnp.asarray(np.where(run, self._lens, np.int32(0))),
                        self._token_vector())
            if self._decode_jit is None:
                donate = self._donate()
                self._decode_jit = self._decode_site.get(
                    _cc.make_key("decode", donate=donate,
                                 mesh=self._mesh_key()),
                    self._build_decode,
                    stable_key=self._aot_key("decode"),
                    example_args=operands, topology=self._topology())
                self._inc("decode_compiles")
        attrs = dict(active=int(run.sum()))
        with timeline.span("serving.decode", **attrs) as sp:
            with timeline.span("serving.decode.dispatch"):
                out = self._decode_jit(*operands)
            if self._inflight:
                self._inc("steps_overlapped")
            slots = np.flatnonzero(run)
            self._enqueued(False, out,
                           [(s, self._slot_req[s]) for s in slots], attrs,
                           sp.id)
            self._inc("decode_steps")
            self._count_quant_matmuls()
            self._lens[slots] += 1
            self._todo[slots] -= 1
        if self.capture_logits:
            self._drain("capture_logits")
        else:
            self._retire(before=self._step_idx)

    def slot_state(self, slot):
        """For a check against a reference, off the serving path: how
        many positions of ``slot``'s request have been folded into what
        a family keeps per slot, and that state as host arrays (the
        family's ``slot_state_of(cfg, pools, slot) -> {name: array}``).
        The host's view is made whole first, so the request's prompt
        and tokens hold those positions and one token more — unless
        that token ended the request, which then no longer has the
        slot."""
        self._drain("slot_state")
        state = self._family.slot_state_of(self.cfg, self._cache_operands(),
                                           slot)
        return int(self._lens[slot]), self._jax.device_get(state)

    def _build_decode(self):
        """The one decode executable: the family's ``decode_paged`` over
        every slot, the whole tuple of ``init_paged_pools`` DONATED and
        updated where it lies — pages and, for a family that keeps state
        per slot, the slot arrays behind them (a slot the step does not
        run has ``lens == 0`` and keeps its state)."""
        jax, jnp = self._jax, self._jnp
        cfg = self.cfg
        cap = self.capture_logits

        if self._pp > 1:
            # stage-partitioned decode: the 1F1B microbatch tick loop
            # inside ONE shard_map (models/gpt_pp.py) — page table,
            # write coordinates and lengths stay traced operands, so
            # this is still the one decode executable forever
            from ..models import gpt_pp
            step_pp = gpt_pp.make_decode_step(
                cfg, self._mesh, self._param_specs, self._pp_microbatch)

            def decode_pp(params, cache_k, cache_v, page_table, wpages,
                          woffs, lens, toks):
                logits, ck, cv = step_pp(params, toks, cache_k, cache_v,
                                         page_table, wpages, woffs, lens)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                cache = self._constrain_cache((ck, cv))
                return (*cache, nxt, *((logits,) if cap else ()),
                        self._replicated(nxt))

            donate = ((1, 2) if _donation_enabled() else ())
            return jax.jit(decode_pp, donate_argnums=donate)

        n = self._n_cache
        family = self._family

        def decode(params, *args):
            page_table, wpages, woffs, lens, toks = args[n:]
            logits, cache, extra = family.decode_paged(
                params, cfg, args[:n], page_table, wpages, woffs, lens,
                toks, mesh=self._mesh)
            with jax.named_scope("head_sample"):
                nxt = back = jnp.argmax(logits, -1).astype(jnp.int32)
                if extra is not None:
                    # what the family counts a step (expert loads) rides
                    # the sampled tokens' readback: one array, one sync
                    back = jnp.concatenate(
                        [nxt, extra.reshape(-1).astype(jnp.int32)])
            cache = self._constrain_cache(cache)
            # last, the vector the next program takes: the tokens alone
            return (*cache, back, *((logits,) if cap else ()),
                    self._replicated(nxt))

        donate = (tuple(range(1, 1 + self._n_cache))
                  if _donation_enabled() else ())
        return jax.jit(decode, donate_argnums=donate)

    def cancel(self, request_id):
        """Base cancel plus the injection queue (a handed-off request
        cancelled before its pages land).  The step in flight is
        committed first, so what the caller learns — cancelled while
        queued, or None: running or finished — holds when it returns."""
        self._drain("cancel")
        out = super().cancel(request_id)
        if out is not None:
            return out
        for req in self._inject_queue:
            if req.id == request_id:
                self._inject_queue.remove(req)
                self._g_queue.set(self._queued_total())
                self._inc("requests_cancelled")
                return req
        return None

    def active_request_ids(self):
        """Base ids plus the injection queue (handed-off requests whose
        pages landed but haven't been admitted yet are still owned
        here — a relaunched router must not re-ship them)."""
        ids = super().active_request_ids()
        seen = set(ids)
        ids += [str(r.id) for r in self._inject_queue
                if str(r.id) not in seen]
        return ids

    # -------------------------------------------------------------- warmup
    def _warmup_wave_len(self, lo, s, mnt):
        """Rungs only reachable by chunk-eligible prompts stay cold on
        the WAVE path (the chunked executable covers those admissions);
        rungs short prompts can still bucket up into get a warmup
        prompt capped at ``prefill_chunk`` so it is not diverted."""
        n = super()._warmup_wave_len(lo, s, mnt)
        if self._prefill_chunk is None:
            return n
        if lo > self._prefill_chunk:
            return None             # every prompt this long chunks
        return min(n, self._prefill_chunk)

    def warmup(self, max_new_tokens=2):
        """Base ladder + decode warmup, plus the paged extras: the COW
        copy executable and (when chunking is on) the chunk executable,
        so steady traffic compiles NOTHING even on first divergence or
        first long prompt.  Artifact-preloaded executables skip their
        warmup work like the base ladder's do.  Warmup's synthetic
        prompt pages are flushed from the prefix cache afterwards —
        they must not shadow real traffic's hits or hold pages."""
        before = self._counts["prefill_compiles"]
        super().warmup(max_new_tokens)
        self._warming = True
        real_max_queue = self.max_queue
        self.max_queue = max(real_max_queue, self.slots,
                             self.batch_buckets[-1])
        try:
            if (self._copy_jit is None
                    and not _cc.artifact_ready(
                        self._aot_key("copy"),
                        topology=self._topology())):
                # scratch-onto-scratch: a no-op copy that only compiles
                # (with an artifact on disk the load happens lazily at
                # the first real COW — a deserialization, not a compile)
                self._set_cache(self._get_copy_jit()(
                    *self._cache_operands(), np.int32(0), np.int32(0)))
            if (self._chunk_jit is None
                    and self._prefill_chunk is not None
                    and self._prefill_chunk + 2 <= self.max_len
                    and not _cc.artifact_ready(
                        self._aot_key("chunk", c=self._prefill_chunk),
                        topology=self._topology())):
                n = self._prefill_chunk + 1      # two chunks: full + tail
                self.submit(np.ones((n,), np.int32), 1)
                self.run()
            if self._handoff or self._host_tier is not None:
                # prime the handoff executables so a disaggregated
                # replica's first extraction/injection is not a compile
                # in live traffic: a scratch-table extract and a
                # zero-payload inject aimed at the scratch page.  A
                # host-tier engine primes BOTH too — spills ride the
                # extract, fault-backs ride the inject
                if (self._extract_jit is None
                        and not _cc.artifact_ready(
                            self._aot_key("extract"),
                            topology=self._topology())):
                    self._extract_slot_kv(0, 0)
                if (self._inject_jit is None
                        and not _cc.artifact_ready(
                            self._aot_key("inject"),
                            topology=self._topology())):
                    zeros = [np.zeros(
                        (p.shape[0], 0) + tuple(p.shape[2:]),
                        np.dtype(p.dtype))
                        for p in self._cache_operands()]
                    self._inject_call(
                        np.zeros((self._pages_per_slot,), np.int32),
                        self._pad_payload(zeros, 0))
        finally:
            self._warming = False
            self.max_queue = real_max_queue
        self._pager.flush_reclaimable()
        return self._counts["prefill_compiles"] - before

    # --------------------------------------------------------------- views
    def _kv_accounting(self):
        """Paged accounting: reserved = pages actually referenced (the
        whole point — idle capacity costs nothing); ``page_utilization``
        is tokens held per in-use page position and can exceed 1.0 when
        prefix sharing packs several requests onto one physical page.

        Bytes derive from the ACTUAL cache arrays (``nbytes``), never an
        assumed 4-byte element — an int8 pool's pages cost 1 byte per
        element PLUS their per-position-per-head scale rows, and both
        halves of that pair count (a page without its scales is not a
        page)."""
        ps = self._page_size
        total = sum(int(a.nbytes) for a in self._page_pools())
        page_bytes = total // self._num_pages
        in_use = self._pager.pages_in_use()
        held = int(self._lens.sum()) + sum(
            int(getattr(r, "_chunk_pos", 0)) for r in self._chunk_jobs)
        return {"kv_bytes_reserved": int(in_use * page_bytes),
                "kv_bytes_total": total,
                # what a cached position NEEDS (the family's count), not
                # what its page row occupies (kv_bytes_total / positions)
                "kv_bytes_per_position": self._family.kv_bytes_per_position(
                    self.cfg, self._pools[0].dtype.itemsize),
                "kv_tokens_held": held,
                "page_utilization": round(held / max(1, in_use * ps), 4)}

    def stats(self):
        # queue_depth comes through _queued_total (inject queue
        # included): drivers polling it — the fleet worker's step loop
        # — must see queued handoffs or a decode replica never steps
        out = super().stats()
        # how often the loop had to make the host's view whole, by what
        # asked for it (beside ``steps_overlapped``: how often it ran on)
        out["drains"] = dict(self._drains)
        # the waves committed so far by the bucket their program ran in:
        # what each was given (requests, prompt tokens), what it paid
        # for (rows = batch x seq) and the device's time for it
        out["prefill_by_bucket"] = {
            k: dict(v) for k, v in sorted(self._prefill_by_bucket.items())}
        pg = self._pager.stats()
        for k in ("prefix_page_hits", "prefix_page_misses", "cow_copies"):
            pg.pop(k)    # the engine-mirrored (warmup-quiet) counts win
        out.update(pg)
        group_of = getattr(self._family, "decode_group_pages", None)
        if group_of is not None:
            # the decode kernel's grid is slots x (table width / G) steps
            # a layer; a step is live while its first row is at or
            # under the slot's length
            G = group_of(self.cfg, self._cache_operands(),
                         self._pages_per_slot, self._tp)
            live = self._lens[self._active] // (G * self._page_size) + 1
            out["paged_attn_group_pages"] = G
            out["paged_attn_live_step_share"] = round(
                int(live.sum())
                / (self.slots * (self._pages_per_slot // G)), 4)
        tier = self._host_tier
        out["host_tier_bytes"] = int(tier.bytes) if tier else 0
        out["host_tier_entries"] = len(tier) if tier else 0
        out["host_tier_fill"] = (
            round(tier.bytes / max(1, tier.limit), 4)
            if tier else 0.0)
        if tier:
            self._g_host_tier.set(tier.bytes)
        # the replica's prefix sketch for the fleet router: short
        # digests of resident (device) and spilled (host) full-page
        # chains, deduped, newest-biased, wire-bounded
        digests = list(self._pager.chain_digests(limit=128))
        if tier:
            digests.extend(tier.digests(limit=64))
        seen, sketch = set(), []
        for d in reversed(digests):
            if d not in seen:
                seen.add(d)
                sketch.append(d)
        out["chain_digests"] = sketch[:160]
        return out
