"""Generative serving engine — prefill/decode dispatch over the paged cache.

The device half of the serving subsystem (docs/SERVING.md): three jitted
functions — built from the programs the MODEL hands over
(``models/served.py``: the engine knows no model by name) — whose
signatures depend ONLY on server-start configuration
(slot capacity, page geometry, prompt bucket) — never on the number of
active sequences — so the RecompileLedger records exactly one
``first_compile`` per function and NO ``new_shape`` events across
admits/evicts (asserted in tests/test_serving.py):

* **prefill** — the whole (padded) prompt through one causal pass of the
  MODEL's prefill program + first-token sampling; returns the per-layer
  cache rows for the scatter. TTFT is measured to this token's read.
* **write-prompt** — write the prefill K/V into the slot's pages, whole
  pages at a time and in place (donated pool; pages past the prompt land
  on the trash page, the tail of the prompt's last page is masked garbage
  until decode overwrites it).
* **decode** — one token for EVERY slot (inactive slots ride along masked:
  they write to the trash page and their outputs are ignored), paged
  attention via the registry's ``paged_decode_attention``, then the
  vectorized temperature/top-k/top-p sampler with per-slot keys split from
  this step's fresh key; inside the one program it runs only the body the
  bank's knobs ask for (``serving/sampling.py``: the argmax alone while no
  slot samples).

The loop (docs/SERVING.md § The loop): no launch needs the VALUE of a
token — the bank's token vector is ``decode``'s own result, with an
admission's first token set in it by ``prefill``, and never leaves the
device; lengths advance at the launch. ``step()`` reads what it launches
before it returns; the worker that ``start()`` runs keeps one decode step in
flight and reads it after it has launched the next one, so that the host's
part of a step runs under the device's time. A sequence complete by its
count leaves its slot with its last token in flight
(``SlotScheduler.leaving``); an ``eos`` read a step late costs one dropped
token of one slot.

With ``prefix_pages > 0`` a fourth compiled function joins them —
**suffix-prefill**: on a radix-prefix-cache hit (``serving/prefix.py``)
the shared pages are mapped into the slot's page-table row by reference
and only the prompt's uncached tail (padded to the static
``suffix_bucket``) is prefilled against the cached prefix K/V, so shared
system prompts admit in O(suffix) instead of O(prompt). All four
signatures stay config-only — prefix hits never recompile.

With ``spec_k > 0`` (plus a ``draft_model``) a fifth joins —
**verify** (the model's ``verify`` program): speculative decoding
(docs/SERVING.md § Speculative decoding, ``serving/speculative.py``).
Each step, greedy slots run K draft-model decode steps (one compiled
``draft_decode`` scan over a dense per-slot draft cache) to propose K
tokens, then ONE target forward over the ``K+1``-token window scores
every proposal; the accepted prefix plus the target's correction/bonus
token commits — 1..K+1 tokens per step per slot, bit-identical to
non-speculative greedy decoding (scoped to verify/decode argmax
agreement across kernels — docs/SERVING.md § Speculative decoding,
"On-device caveat"). A rejection REWINDS the slot's cached length (and
the draft's) instead of freeing pages, so rollback is O(1) and
refcount-safe. Slots with ``temperature > 0`` (or
``spec_disabled`` requests) fall back to the plain decode step. Verify's
shape depends only on ``(max_slots, spec_k, page geometry)`` — the
ledger stays at one ``first_compile`` per function, zero ``new_shape``.

A model whose programs yield a BLOCK a step (``ServingPrograms.block`` > 1:
generation by diffusion over blocks, ``models/sdar.py``) takes the
same loop with the block model's programs (``models/served.py``): its
``prefill`` writes the prompt's whole blocks and yields no token; its
``decode`` denoises one block for every slot in one program and stores the
clean block's rows. The bank holds each slot's next block as it starts (the
mask token, or a first block's fixed prompt tail) instead of a token; a
step reserves pages to the block's end, advances a slot's cached length by
the block and commits up to the block's tokens a slot, stamped together,
cutting at an ``eos`` or at ``max_new_tokens``. Nothing of it needs a
token's value, so the worker runs it ahead as it runs the one-token step.

Observability (docs/OBSERVABILITY.md catalog additions): admitted/evicted/
generated-token counters, slot-occupancy gauge, decode-step latency
histogram, TTFT + inter-token histograms, ``serving_prefill``/
``serving_decode`` spans, and ledger notes on both compiled functions.

**Supervision** (docs/ROBUSTNESS.md): a decode-step exception or worker
death no longer kills the engine. The supervisor frees every slot,
re-queues requests with retry budget left (front of the queue, original
submit time), completes the rest terminally as ``error``, reallocates the
possibly-donated KV buffer (same shape — the cached jit functions survive,
so recovery shows ZERO ``new_shape`` ledger events), and restarts the
worker under capped exponential backoff up to ``max_restarts``. Per-request
deadlines retire overdue work as ``deadline`` whether queued or mid-decode,
and a bounded pending queue (``max_queue``) sheds over-capacity
submissions immediately as ``shed`` — every submitted request reaches a
terminal finish reason, which is the property the ``chaos`` gate stage
asserts under an injected fault schedule (deeplearning4j_tpu/faults/).
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import faults, observe
from deeplearning4j_tpu.observe.tracing import host_since, host_usage
from deeplearning4j_tpu.ops.pallas_attention import (
    gather_pages, paged_decode_groups)
from deeplearning4j_tpu.models.served import SlotState
from deeplearning4j_tpu.serving.cache import PagedKVCache, SlotStatePool
from deeplearning4j_tpu.serving.prefix import PrefixMatch, RadixPrefixCache
from deeplearning4j_tpu.serving.speculative import SpeculativeDecoder
from deeplearning4j_tpu.serving.sampling import (
    SAMPLER_PATHS, sample_tokens, sampler_path)
from deeplearning4j_tpu.serving.scheduler import (
    GenerationRequest, GenerationResult, SlotScheduler, count_terminal,
    note_terminal)

logger = logging.getLogger(__name__)

# keys split ahead of their use, while the device runs a decode step: one for
# the next step and a few for the admissions before it
_KEY_RESERVE = 4


class _Admission(NamedTuple):
    """A launched prefill whose first token the host has not read yet."""

    st: Any                # the scheduler's state of the sequence
    parent: Optional[int]  # the ``serving_admit`` span it was launched under
    tok: Any               # the sampled token, on the device
    stats: Any             # the model's statistics, on the device (or None)
    launch: tuple          # perf_counter readings around the launch
    args: dict             # what ``serving_prefill`` carries


class _DecodeStep(NamedTuple):
    """A launched decode step whose tokens the host has not read yet."""

    slots: list            # (slot, its state) the step was launched for
    toks: Any              # the bank's token vector after the step, device
    stats: Any
    span: Any              # the step's ``serving_decode`` span
    # a block step's (``_step_block``): the pass that unmasked each token and
    # the passes run, on the device; a slot's (fixed positions, tokens it
    # yields), aligned with ``slots``
    when: Any = None
    passes: Any = None
    yields: Optional[list] = None


def build_write(page: int, trash: int):
    """The jitted ``write_prompt``: a prefill's cache rows ``(L, sides, T,
    width)`` go into the donated pool as WHOLE pages, in place. The prompt's last
    page carries the padded positions' rows past ``prompt_len`` — garbage
    that attention masks and decode overwrites; pages past the prompt go to
    the trash page. Only pages the slot owns alone are written: a full
    prefill runs only without a prefix match, so its pages are all fresh.
    A function of the page geometry alone, so it can be lowered for a
    described device without an engine (tests/test_tpu_compile.py)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def write_prompt(kv_pages, kv_prompt, pt_row, prompt_len):
        n_l, sides, t, width = kv_prompt.shape
        n = -(-t // page)
        pages = jnp.pad(kv_prompt, ((0, 0), (0, 0), (0, n * page - t),
                                    (0, 0))).reshape(n_l, sides, n, page,
                                                     width)
        page_idx = jnp.where(jnp.arange(n) * page < prompt_len,
                             pt_row[:n], trash)
        return kv_pages.at[:, :, page_idx].set(pages)

    return write_prompt


def build_prefill(model_prefill):
    """The jitted ``prefill``: the model's ``prefill`` program over one
    padded prompt, and the sampler on its last position's logits. The
    sampled token also joins the bank's token vector at ``slot`` on the
    device, where the next ``decode`` finds it: no launch waits for the host
    to have read it. Needs no engine, like :func:`build_write`."""

    @jax.jit
    def prefill(params, ids, prompt_len, key, temp, top_k, top_p, bank_toks,
                slot):
        last, rows, stats = model_prefill(params, ids, prompt_len)
        tok = sample_tokens(last, key, temp, top_k, top_p)[0]
        # (L, sides, T, width), scalar, statistics, (S,)
        return rows, tok, stats, bank_toks.at[slot].set(tok)

    return prefill


def build_decode(decode_step, page: int, trash: int):
    """The jitted ``decode``: one token for every slot against the donated
    pool (the model's ``decode_step`` program) and the sampler. Like
    :func:`build_write`, a function of configuration and page geometry
    alone. ``tokens`` is the bank's token vector as the step before (or an
    admission's ``prefill``) left it on the device, and the result takes its
    place: a slot that sat this step out keeps its token. The model's
    statistics (``None`` for a model without an expert layer) ride out
    beside the tokens."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, kv_pages, page_table, seq_lens, tokens, active,
               key, temp, top_k, top_p):
        s_n = tokens.shape[0]
        on = active > 0
        write_page = jnp.where(
            on, page_table[jnp.arange(s_n), seq_lens // page], trash)
        write_off = seq_lens % page
        seq_incl = seq_lens + on.astype(jnp.int32)
        kv_pages, logits, stats = decode_step(
            params, kv_pages, tokens, seq_lens, page_table, seq_incl,
            write_page, write_off)
        toks = sample_tokens(logits, key, temp, top_k, top_p)
        return kv_pages, jnp.where(on, toks, tokens), logits, stats

    return decode


def build_block_decode(decode_step, page: int, trash: int):
    """The jitted ``decode`` of a block model (``ServingPrograms.block`` > 1): one
    block for every slot against the donated pool (the model's
    ``decode_step``), its rows written where the slot's pages hold the
    block's positions (the trash page for a slot that sits the step out). A
    function of configuration and page geometry alone, like
    :func:`build_decode`; greedy, so no key and no sampler."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, kv_pages, page_table, seq_lens, block_init, active):
        s_n, b = block_init.shape
        on = active > 0
        pos = seq_lens[:, None] + jnp.arange(b)[None, :]
        row = jnp.clip(pos // page, 0, page_table.shape[1] - 1)
        write_page = jnp.where(
            on[:, None], page_table[jnp.arange(s_n)[:, None], row], trash)
        return decode_step(params, kv_pages, block_init, seq_lens,
                           page_table, write_page, pos % page, on)

    return decode


def build_state_write():
    """The jitted ``write_prompt`` of a model whose cache is a state a slot
    (``models/served.py`` ``SlotState``): a prefill's state, every array of
    it whole, takes slot ``slot``'s place in the donated pool, in place.
    Whatever the slot held before is gone: this write is its reset."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def write_prompt(pool, state, slot):
        return {name: jax.lax.dynamic_update_index_in_dim(
            pool[name], state[name].astype(pool[name].dtype), slot, 0)
            for name in pool}

    return write_prompt


def build_state_decode(decode_step):
    """The jitted ``decode`` over a pool of slot states: :func:`build_decode`
    without the page table. A slot that is not active keeps its state (the
    model's ``decode_step`` sees to it) and its token."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pool, seq_lens, tokens, active, key, temp, top_k,
               top_p):
        on = active > 0
        pool, logits, stats = decode_step(params, pool, tokens, seq_lens, on)
        toks = sample_tokens(logits, key, temp, top_k, top_p)
        return pool, jnp.where(on, toks, tokens), logits, stats

    return decode


class GenerativeEngine:
    """Continuous-batching text generation over any model handle that
    speaks the protocol of ``models/served.py``, with rows a token in pages
    or a state a slot, as its ``cache_rows()`` says.

    Synchronous use (tests, batch jobs)::

        eng = GenerativeEngine(model, max_slots=4)
        results = eng.generate([prompt1, prompt2], max_new_tokens=32)

    Serving use (the ``ParallelInference`` shape)::

        eng.start()
        fut = eng.submit(prompt, temperature=0.8, top_p=0.95)
        result = fut.result()
        eng.stop()
    """

    def __init__(self, model, *, max_slots: int = 4,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_pages_per_seq: int = 8, max_prompt: int = 32,
                 seed: int = 0, supervise: bool = True,
                 max_restarts: int = 3, restart_backoff_s: float = 0.05,
                 max_backoff_s: float = 2.0, max_queue: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 prefix_pages: int = 0,
                 suffix_bucket: Optional[int] = None,
                 prefix_min_match: Optional[int] = None,
                 spec_k: int = 0,
                 draft_model=None,
                 engine_id: int = 0):
        cfg = model.cfg
        self.programs = model.serving_programs()
        if prefix_pages and self.programs.prefill_suffix is None:
            raise ValueError(
                f"prefix_pages={prefix_pages} needs a suffix-prefill program "
                f"and {type(model).__name__} has none (docs/SERVING.md)")
        if spec_k and self.programs.verify is None:
            raise ValueError(
                f"spec_k={spec_k} needs a verify program and "
                f"{type(model).__name__} has none (docs/SERVING.md)")
        if max_prompt > cfg.max_position:
            # a prefill's position gather would silently CLAMP indices
            # past max_position — reject the misconfiguration instead
            raise ValueError(
                f"max_prompt={max_prompt} exceeds the model's "
                f"max_position={cfg.max_position}")
        self.model = model
        self.cfg = cfg
        self.max_prompt = int(max_prompt)
        if num_pages is None:
            # full reservation by default; oversubscribe explicitly to make
            # the free-list pressure (oom evictions) reachable. A prefix
            # cache gets its page budget ON TOP so the tree never starves
            # the slot bank by default.
            num_pages = max_slots * max_pages_per_seq + max(0, prefix_pages)
        rows = model.cache_rows()
        # tokens a step yields a sequence: 1, or a block model's block
        self.block = int(self.programs.block)
        if self.block > 1 and (prefix_pages or spec_k):
            raise ValueError(
                f"{type(model).__name__} yields a block of {self.block} "
                f"tokens a step: no prefix cache and no speculation for it "
                f"(docs/SERVING.md)")
        if isinstance(rows, SlotState):
            # a state a slot: no page to hand out, every array in the dtype
            # the model names; the context limit is the paged arithmetic
            self.cache = SlotStatePool(
                state=rows, page_size=page_size, max_slots=max_slots,
                max_pages_per_seq=max_pages_per_seq)
        else:
            self.cache = PagedKVCache(
                layers=rows.layers, sides=rows.sides, row_width=rows.width,
                page_size=page_size,
                num_pages=num_pages, max_slots=max_slots,
                max_pages_per_seq=max_pages_per_seq,
                dtype=jax.tree.leaves(model.params)[0].dtype)
        if self._first_need(self.max_prompt) > self.cache.max_context():
            raise ValueError(
                f"max_prompt={max_prompt} + "
                f"{'1' if self.block == 1 else 'a block'} exceeds per-slot "
                f"context {self.cache.max_context()} "
                f"(page_size*max_pages_per_seq)")
        self.scheduler = SlotScheduler(max_slots)
        # ---------------------------------------- radix prefix cache (2a)
        # prefix_pages > 0 enables shared-prompt KV reuse: a radix tree
        # over token sequences whose nodes hold refcounted cache pages
        # (docs/SERVING.md § Radix prefix cache). suffix_bucket is the
        # compiled suffix-prefill width — a hit whose uncached tail
        # exceeds it falls back to the full prefill (static shapes keep
        # the compile-once property: zero new_shape, test-asserted).
        self.prefix: Optional[RadixPrefixCache] = None
        self.suffix_bucket = min(self.max_prompt,
                                 int(suffix_bucket) if suffix_bucket
                                 else 2 * self.cache.page_size)
        if prefix_pages:
            self.prefix = RadixPrefixCache(
                self.cache, max_pages=int(prefix_pages),
                min_match=prefix_min_match)
        # ------------------------------------------ speculative decoding (2b)
        # spec_k > 0 (plus a draft model sharing the target's vocab) turns
        # greedy slots speculative: K draft proposals per step, one target
        # verify pass, 1..K+1 committed tokens (docs/SERVING.md
        # § Speculative decoding). Off by default — spec_k=0 is the plain
        # one-token decode loop, byte-for-byte.
        self.spec: Optional[SpeculativeDecoder] = None
        self._spec_slots: set = set()
        self._spec_limit = 0
        if spec_k:
            if draft_model is None:
                raise ValueError("spec_k > 0 requires a draft_model "
                                 "(models.GPT(...).init_draft() builds the "
                                 "paired one)")
            dcfg = draft_model.cfg
            if dcfg.vocab_size != cfg.vocab_size:
                # draft proposals are TARGET token ids — a vocab mismatch
                # would silently verify garbage
                raise ValueError(
                    f"draft vocab_size={dcfg.vocab_size} != target "
                    f"vocab_size={cfg.vocab_size}")
            if dcfg.eos_token != cfg.eos_token:
                # eos rides the request, but a config disagreement is a
                # mispairing worth failing fast on (draft_config_for's
                # contract: vocab/eos/positions agree)
                raise ValueError(
                    f"draft eos_token={dcfg.eos_token} != target "
                    f"eos_token={cfg.eos_token}")
            self.spec = SpeculativeDecoder(
                draft_model, k=int(spec_k), max_slots=max_slots,
                max_ctx=self.cache.max_context(),
                max_prompt=self.max_prompt)
            self._spec_limit = min(cfg.max_position, dcfg.max_position)
        self._key = jax.random.key(seed)
        # key-hygiene audit trail: every key handed to a jitted sampler,
        # bounded; tests assert no value ever repeats. The keys stay on the
        # device until ``key_trail`` is read: no step waits for one
        self._key_trail: deque = deque(maxlen=4096)
        self._key_reserve: deque = deque()
        # name -> (host values, their copy on the device): _resident
        self._resident_args: dict = {}
        # (the decode bank's sampling settings on the device, the sampler
        # body they ask for): worked out again only when _resident has sent
        # one of the three anew
        self._sampler: tuple = ((None,) * 3, SAMPLER_PATHS[0])
        # the bank's token vector: every slot's newest token, where the
        # launches leave it on the device (decode's result, with an
        # admission's first token set by its prefill); a host array only
        # until the first launch
        self._toks: Any = np.zeros((max_slots,), np.int32)
        # a block model's bank: every slot's next block as it starts, the
        # mask token where masked (a first block's prompt tail is fixed)
        self._block_init = (np.full((max_slots, self.block),
                                    cfg.mask_token_id, np.int32)
                            if self.block > 1 else None)
        # launched and not read yet (docs/SERVING.md § The loop): the decode
        # step the worker keeps in flight, and this iteration's admissions
        self._flying: Optional[_DecodeStep] = None
        self._landing: List[_Admission] = []
        self._prefill_fn = None
        self._write_fn = None
        self._decode_fn = None
        self._suffix_fn = None
        self._verify_fn = None
        # per-slot prefix match staged between _admit_pages and
        # _prefill_into — set (or cleared) on EVERY admission, so a crash
        # between the two can never leak a stale match into the slot's
        # next tenant. Kept out of _prefill_into's signature: the
        # robustness tests wrap that method with (slot, req) shims.
        self._slot_match: dict = {}
        self._worker: Optional[threading.Thread] = None
        self._stop_flag = False
        self._error: Optional[Exception] = None
        # ------------------------------------------ supervisor configuration
        self.supervise = bool(supervise)
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.default_deadline_s = default_deadline_s
        self.restarts = 0            # lifetime crash recoveries (<= cap)
        self._step_count = 0         # running number of step(), for spans
        self.stopped_cleanly = True  # last stop() joined its worker in time
        # ------------------------------------------------- cluster membership
        # engine_id names this engine inside a ClusterRouter
        # (serving/cluster.py); on_unrecoverable, when set, is called ONCE
        # from the dying worker thread after the restart budget is spent —
        # the router's hook drains this scheduler and migrates retryable
        # requests to a surviving engine BEFORE fail_all retires the rest.
        self.engine_id = int(engine_id)
        self.on_unrecoverable: Optional[Callable[[Exception], None]] = None
        self._lifecycle = threading.Lock()  # guards _worker hand-off
        m = observe.metrics()
        self._obs = {
            "admitted": m.counter("dl4j_tpu_serving_admitted_total"),
            "generated": m.counter("dl4j_tpu_serving_generated_tokens_total"),
            "occupancy": m.gauge("dl4j_tpu_serving_slot_occupancy"),
            "decode_h": m.histogram("dl4j_tpu_serving_decode_step_seconds"),
            "ttft_h": m.histogram("dl4j_tpu_serving_ttft_seconds"),
            "itl_h": m.histogram("dl4j_tpu_serving_intertoken_seconds"),
            "queue_wait_h": m.histogram(
                "dl4j_tpu_serving_queue_wait_seconds"),
            "restarts": m.counter("dl4j_tpu_serving_engine_restarts_total"),
            "retries": m.counter("dl4j_tpu_serving_retries_total"),
            "sampler": {path: m.counter(
                "dl4j_tpu_serving_sampler_steps_total", path=path)
                for path in SAMPLER_PATHS},
            "launches": [m.counter(
                "dl4j_tpu_serving_decode_launches_total", ahead=str(a))
                for a in (0, 1)],
            "paged_groups": m.counter("dl4j_tpu_paged_decode_groups_total"),
            # written ONLY by stop(): the gauge is process-global, and a
            # constructor write here would clobber a previous engine's
            # hung-stop indication while that engine is still wedged
            "stopped_g": m.gauge("dl4j_tpu_serving_stopped_cleanly"),
        }
        # AOT warm boot (serving/aot.py): with $DL4J_TPU_COMPILE_CACHE
        # set, every compiled-fn slot fills from the persistent export
        # cache BEFORE the first request — or, on a cache miss, compiles
        # now and persists for the next process. Inert without the env.
        from deeplearning4j_tpu.serving import aot as _aot

        _aot.maybe_warm_boot(self)

    # ------------------------------------------------------------------ keys
    def _split_key(self):
        with observe.tracer().span("serving_next_key", category="serving"):
            self._key, sub = jax.random.split(self._key)
        return sub

    def _next_key(self):
        """Hand out a fresh subkey of the root key — the ONLY way keys leave
        the engine, so the audit trail sees every one exactly once. The
        split (three eager dispatches, 0.8 ms on the v5e's host) is made
        ahead by :meth:`_reserve_keys` where it can be; the chain of splits,
        and so every key and their order, is what it is one at a time."""
        sub = (self._key_reserve.popleft() if self._key_reserve
               else self._split_key())
        self._key_trail.append(sub)
        return sub

    def _reserve_keys(self) -> None:
        """Split the next keys now. Called between a decode step's launch
        and its read: the device is busy and the host would only wait."""
        while len(self._key_reserve) < _KEY_RESERVE:
            self._key_reserve.append(self._split_key())

    def _resident(self, name: str, host: np.ndarray):
        """The device's copy of a host argument that seldom changes from one
        call to the next (a full bank's active mask, the sampling settings):
        transferred again only when its values change (0.14 ms an argument
        a call on the v5e's host, with the device idle)."""
        held = self._resident_args.get(name)
        if held is None or not np.array_equal(held[0], host):
            held = self._resident_args[name] = (host, jax.device_put(host))
        return held[1]

    @property
    def key_trail(self) -> List[bytes]:
        """The raw key data of the newest keys handed out, oldest first."""
        return [np.asarray(jax.random.key_data(k)).tobytes()
                for k in self._key_trail]

    # ---------------------------------------------------------- compiled fns
    def _build_prefill(self):
        if self.block > 1:
            return jax.jit(self.programs.prefill)
        return build_prefill(self.programs.prefill)

    def _build_write(self):
        if isinstance(self.cache, SlotStatePool):
            return build_state_write()
        return build_write(self.cache.page_size, self.cache.trash_page)

    def _build_suffix(self):
        """Suffix-only prefill for prefix-cache hits: gather the cached
        prefix K/V out of the slot's pages, run the (bucketed) suffix
        through the model's ``prefill_suffix`` program, sample the first token from
        the last suffix position, and scatter the suffix K/V back into
        the pages. Shapes depend only on server config (max_prompt,
        suffix_bucket, page geometry) — ONE first_compile, zero
        new_shape, same as the other three."""
        cache, model_suffix = self.cache, self.programs.prefill_suffix
        page, trash = cache.page_size, cache.trash_page
        t_pre = self.max_prompt
        n_pre = cache.pages_for(t_pre)
        sides = range(cache.sides)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def suffix_prefill(params, kv_pages, ids, prefix_len, suffix_len,
                           pt_row, key, temp, top_k, top_p, bank_toks, slot):
            # the prompt bucket's whole pages, gathered a layer and side at
            # a time (as the pool is written below, and for the same
            # reason): (L, 2, n, page, E) -> (L, 2, Tpre, E)
            run = jnp.stack([
                jnp.stack([gather_pages(kv_pages, li, side, pt_row[:n_pre])
                           for side in sides])
                for li in range(cache.layers)])
            prefix_kv = run.reshape(run.shape[:2] + (n_pre * page, -1))
            prefix_kv = prefix_kv[:, :, :t_pre]
            logits, kv_suf = model_suffix(
                params, ids, prefix_kv, prefix_len, suffix_len)
            last = logits[0, suffix_len - 1][None]  # (1, V)
            tok = sample_tokens(last, key, temp, top_k, top_p)[0]
            b = ids.shape[1]
            apos = prefix_len + jnp.arange(b)
            valid = jnp.arange(b) < suffix_len
            row_idx = jnp.clip(apos // page, 0, pt_row.shape[0] - 1)
            wpage = jnp.where(valid, pt_row[row_idx], trash)
            # one scatter a layer and side, the form the decode step's
            # writes take: a scatter over the leading axes too makes the
            # TPU compiler copy the whole pool into another layout and back
            for li in range(cache.layers):
                for side in sides:
                    kv_pages = kv_pages.at[li, side, wpage, apos % page].set(
                        kv_suf[li, side])
            return kv_pages, tok, bank_toks.at[slot].set(tok)

        return suffix_prefill

    def _build_decode(self):
        if self.block > 1:
            return build_block_decode(self.programs.decode_step,
                                      self.cache.page_size,
                                      self.cache.trash_page)
        if isinstance(self.cache, SlotStatePool):
            return build_state_decode(self.programs.decode_step)
        return build_decode(self.programs.decode_step, self.cache.page_size,
                            self.cache.trash_page)

    def _build_verify(self):
        """Speculative verification (docs/SERVING.md § Speculative
        decoding): ONE target forward over each slot's ``spec_k + 1``
        fed tokens (last committed + K draft proposals) against the paged
        cache, returning the target's greedy argmax at every fed
        position. Inactive/non-speculating slots ride along masked —
        their writes land on the trash page, their outputs are ignored.
        Shapes depend only on (max_slots, spec_k, page geometry): ONE
        first_compile, zero new_shape, same as the other four."""
        cache, model_verify = self.cache, self.programs.verify
        page, trash = cache.page_size, cache.trash_page

        @functools.partial(jax.jit, donate_argnums=(1,))
        def verify(params, kv_pages, tokens, seq_lens, page_table, active):
            s_n, b = tokens.shape
            on = active > 0
            pos = seq_lens[:, None] + jnp.arange(b)[None, :]
            row = jnp.clip(pos // page, 0, page_table.shape[1] - 1)
            wpage = jnp.where(
                on[:, None],
                page_table[jnp.arange(s_n)[:, None], row], trash)
            return model_verify(params, kv_pages, tokens, seq_lens,
                                page_table, wpage, pos % page,
                                page_size=page)

        return verify

    # ------------------------------------------------------------------- api
    def submit(self, prompt, *, max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_token: Optional[int] = None,
               deadline_s: Optional[float] = None, max_retries: int = 1,
               priority: int = 1, slo_class: str = "standard"
               ) -> "Future[GenerationResult]":
        """Queue one generation; returns a Future (thread-safe). A stopped
        engine rejects new work — build a fresh one.

        ``deadline_s`` bounds submit->terminal wall time (engine default
        when None); ``max_retries`` is this request's crash re-admission
        budget (docs/ROBUSTNESS.md). ``priority`` orders the pending queue
        (lower admits first; ties FIFO) and ``slo_class`` labels the
        request for the SLO frontend's metrics — plain callers can ignore
        both. When the pending queue is at ``max_queue``, the request is
        SHED: the future completes immediately with the terminal reason
        ``"shed"`` — callers always get a terminal state, never a hang."""
        eos = self.cfg.eos_token if eos_token is None else eos_token
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = GenerationRequest(
            prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, eos_token=eos,
            deadline_s=deadline_s, max_retries=max_retries,
            priority=priority, slo_class=slo_class)
        return self.submit_request(req)

    def validate_request(self, req: GenerationRequest) -> None:
        """Raise on a request this engine can never serve. Shared by
        :meth:`submit_request` and the SLO frontend, which must validate
        BEFORE displacing queued work to make room for an arrival."""
        if req.prompt.size > self.max_prompt:
            raise ValueError(
                f"prompt length {req.prompt.size} exceeds the engine's "
                f"prefill bucket max_prompt={self.max_prompt}")
        lo, hi = int(req.prompt.min()), int(req.prompt.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            # the embedding gather would silently clamp/wrap out-of-range
            # ids into plausible-but-wrong generations
            raise ValueError(
                f"prompt token ids must be in [0, {self.cfg.vocab_size}), "
                f"got range [{lo}, {hi}]")
        if self.block > 1:
            if req.temperature > 0.0:
                raise ValueError(
                    f"{type(self.model).__name__} denoises by the static "
                    f"low-confidence schedule, which is greedy: sampled "
                    f"requests (temperature {req.temperature}) are not "
                    f"served")
            if np.any(req.prompt == self.cfg.mask_token_id):
                # a masked position of the first block is what the program
                # fills: a prompt that holds the mask token would be
                # rewritten
                raise ValueError(
                    f"prompt holds the mask token "
                    f"{self.cfg.mask_token_id}")

    def submit_request(self, req: GenerationRequest
                       ) -> "Future[GenerationResult]":
        """Queue a pre-built :class:`GenerationRequest` (the SLO frontend's
        entry point — it constructs requests carrying class/priority/
        degradation state). Same contract as :meth:`submit`."""
        if self._error is not None:
            raise RuntimeError("engine loop died") from self._error
        if self._stop_flag:
            raise RuntimeError("engine stopped — submit rejected")
        if req.deadline_s is None:
            req.deadline_s = self.default_deadline_s
        self.validate_request(req)
        if (self.max_queue is not None
                and len(self.scheduler.pending) >= self.max_queue):
            # admission gate: shedding is a TERMINAL result, not an
            # exception — overload is an expected state the SLO frontend
            # steers by, and every caller still gets a definitive answer
            fut: "Future[GenerationResult]" = Future()
            self._finish_unslotted(req, fut, "shed")
            return fut
        fut = self.scheduler.submit(req)
        if self._error is not None:
            # the loop died between the checks above and our enqueue — its
            # fail_all may have drained pending before we appended; fail
            # everything (incl. this future) so result() can never hang
            self.scheduler.fail_all(RuntimeError("engine loop died"))
        elif self._stop_flag:
            # stop() started concurrently and may still be JOINING a live
            # worker: rescue only the queued (never-admitted) futures —
            # touching active slots here would race the worker's step,
            # corrupt page accounting, and burn a restart on a KeyError.
            # stop() itself retires the active slots after the join.
            self.scheduler.fail_pending(RuntimeError("engine stopped"))
        return fut

    def generate(self, prompts: Sequence, **kw) -> List[GenerationResult]:
        """Synchronous batch generation: submit everything, run the
        scheduler loop inline until drained. Crash recovery applies here
        too (same supervisor, no worker thread): a step that dies inside
        the retry budget re-admits and continues; past the budget the
        original exception propagates to the caller."""
        # graftlock: justified(GL012): advisory mode check — start_serving/stop are caller-serialized
        if self._worker is not None:
            raise RuntimeError("generate() is the inline mode — the engine "
                               "is already running a serving loop; use "
                               "submit()")
        futs = [self.submit(p, **kw) for p in prompts]
        while self.scheduler.has_work():
            try:
                self.step()
            except Exception as e:
                if not self._recover(e):
                    self._die(e)
                    raise
        return [f.result() for f in futs]

    def start(self) -> "GenerativeEngine":
        with self._lifecycle:
            if self._worker is not None:
                return self
            self._stop_flag = False
            self._worker = threading.Thread(target=self._serve_loop,
                                            daemon=True)
            self._worker.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the serving loop. In-flight sequences retire with their
        partial output and the documented ``"stopped"`` reason; queued
        requests fail. A worker that does not join within ``timeout``
        (a stuck decode step) is detected and reported — logged ONCE,
        ``stopped_cleanly`` False, ``dl4j_tpu_serving_stopped_cleanly``
        gauge 0 — instead of silently abandoning the thread; the engine
        is left not restartable and active-slot futures stay with the
        stuck worker (completing them here would race it)."""
        self._stop_flag = True
        while True:
            with self._lifecycle:
                w = self._worker
            if w is None or w is threading.current_thread():
                break
            w.join(timeout=timeout)
            if w.is_alive():
                # do NOT null _worker: a restart would race the stuck
                # thread over the same cache/scheduler (double page frees,
                # double-donated kv buffer)
                self.stopped_cleanly = False
                self._obs["stopped_g"].set(0.0)
                logger.error(
                    "serving loop still running after %.0fs (a decode step "
                    "is stuck); engine left stopping, not restartable — "
                    "failing queued requests only", timeout)
                observe.log_event("engine_stop_hung", timeout_s=timeout)
                self.scheduler.fail_pending(
                    RuntimeError("GenerativeEngine stop timed out with the "
                                 "worker hung; queued request failed"),
                    reason="stopped")
                return
            with self._lifecycle:
                if self._worker is w:
                    self._worker = None
                    break
                # a crash-recovery respawn won the hand-off before we set
                # the flag — loop again and join the replacement too
        self.stopped_cleanly = True
        self._obs["stopped_g"].set(1.0)
        try:
            # the tokens of what the worker had launched belong to the
            # partial results (and complete the sequences that only waited
            # for their last one)
            self._land()
        except Exception:
            logger.exception("the step in flight at stop() could not be "
                             "read; its tokens are dropped")
            self._flying = None
            self._landing.clear()
        # in-flight sequences retire with their partial output and the
        # documented "stopped" reason (the worker is joined — no race);
        # queued-but-never-admitted requests fail
        for slot in self.scheduler.active_slots():
            self._retire(slot, "stopped")
        self.scheduler.fail_all(
            RuntimeError("GenerativeEngine stopped before this request "
                         "completed"), reason="stopped")

    def _serve_loop(self) -> None:
        # the empty polls are coalesced into ONE serving_idle span, from the
        # first of them to the first step with work: an idle engine must not
        # write a thousand spans a second into a bounded buffer
        idle_since: Optional[float] = None

        def end_idle() -> None:
            nonlocal idle_since
            if idle_since is not None:
                observe.tracer().complete_between(
                    "serving_idle", idle_since, time.perf_counter(),
                    category="serving")
                idle_since = None

        while not self._stop_flag:
            if not self.scheduler.has_work():
                if idle_since is None:
                    idle_since = time.perf_counter()
                time.sleep(1e-3)
                continue
            end_idle()
            try:
                if faults.should_fire("engine_death"):
                    # a HARD whole-engine kill: spend the restart budget
                    # first so _recover cannot resurrect the worker — the
                    # cluster router (serving/cluster.py) owns this
                    # failure domain, not the supervisor
                    self.restarts = self.max_restarts
                    raise faults.InjectedFault("engine_death")
                faults.maybe_fail("worker_death")
                # one decode step ahead of the tokens' read, unless the
                # next lengths wait for them (speculation)
                self._iterate(ahead=self.spec is None)
            except Exception as e:
                if self._recover(e):
                    # this worker retires; a REPLACEMENT thread owns the
                    # loop from here (observable restart: new thread, new
                    # ident, engine_restarts_total incremented) — unless
                    # stop() raced us, in which case it joins this thread
                    # and finds no work to hand over
                    with self._lifecycle:
                        if self._stop_flag:
                            return
                        self._worker = threading.Thread(
                            target=self._serve_loop, daemon=True)
                        self._worker.start()
                    return
                logger.exception("serving loop died (unrecoverable)")
                self._die(e)
                return
        end_idle()

    # ------------------------------------------------------------ supervisor
    def _die(self, exc: Exception) -> None:
        """Unrecoverable escalation: mark the engine dead, give a cluster
        router's ``on_unrecoverable`` hook one shot at migrating this
        scheduler's requests onto a surviving engine (the hook runs on the
        dying worker thread, after the last step — nothing races it), then
        fail whatever the hook left behind. Without a hook this is exactly
        the old fail-everything path."""
        self._error = exc
        observe.log_event("engine_dead", engine=self.engine_id,
                          restarts=self.restarts, error=repr(exc))
        self._drop_unlanded()
        hook = self.on_unrecoverable
        if hook is not None:
            try:
                hook(exc)
            except Exception:
                logger.exception("on_unrecoverable hook failed; failing "
                                 "the remaining requests terminally")
        self.scheduler.fail_all(exc)

    def adopt_requests(self, items: Sequence[tuple]) -> None:
        """Splice migrated ``(request, future, submit_t)`` tuples — a dead
        sibling's in-flight and queued work, handed over by the cluster
        router — onto the FRONT of the pending queue, preserving their
        order. The tuples keep their ORIGINAL futures, submit times and
        priorities: deadlines keep counting across the migration and
        ``peek_best_pending`` ordering never inverts (the PR-10/11
        re-admission discipline, now cluster-wide). Mirrors
        :meth:`submit_request`'s post-enqueue race handling so an adopted
        future can never hang on an engine that died or stopped under us."""
        items = list(items)
        if not items:
            return
        sched = self.scheduler
        with sched._plock:
            # appendleft reverses; iterate reversed so items[0] ends up
            # at the very front (it was the oldest in-flight request)
            for item in reversed(items):
                sched.pending.appendleft(item)
        if self._error is not None:
            sched.fail_all(RuntimeError("engine loop died"))
        elif self._stop_flag:
            sched.fail_pending(RuntimeError("engine stopped"))

    def _finish_unslotted(self, req, fut, reason: str,
                          submit_t: Optional[float] = None) -> None:
        """Complete a future that never held (or no longer holds) a slot
        with a terminal result: shed at admission, deadline in queue,
        error past the retry budget."""
        if not fut.done():
            fut.set_result(GenerationResult(
                tokens=np.zeros((0,), np.int32), finish_reason=reason,
                prompt_len=int(req.prompt.size), ttft_s=None,
                intertoken_s=[], slo_class=req.slo_class,
                degraded=req.degraded, spec_disabled=req.spec_disabled))
        count_terminal(reason)
        note_terminal(req, submit_t, reason)
        observe.log_event("serving_terminal", reason=reason,
                          slo_class=req.slo_class)

    def _requeue_or_fail(self, st) -> None:
        """A sequence the crash took: back to the FRONT of the queue with
        its original submit time while it has retry budget left (the
        deadline keeps counting across the crash; generation restarts from
        the prompt), else terminally ``error``."""
        req = st.request
        if req.retries_used < req.max_retries:
            req.retries_used += 1
            self._obs["retries"].inc()
            with self.scheduler._plock:
                self.scheduler.pending.appendleft(
                    (req, st.future, st.submit_t))
        else:
            self._finish_unslotted(req, st.future, "error", st.submit_t)

    def _drop_unlanded(self) -> None:
        """Forget what was launched and not read (a crash: its values may
        never come), and re-queue the sequences that had left their slots
        and only waited for their last token, oldest first."""
        sched = self.scheduler
        self._flying = None
        self._landing.clear()
        self._toks = np.zeros((self.cache.max_slots,), np.int32)
        if self._block_init is not None:
            self._block_init[:] = self.cfg.mask_token_id
        leaving, sched.leaving = sched.leaving, []
        for st in reversed(leaving):
            self._requeue_or_fail(st)

    def _recover(self, exc: Exception) -> bool:
        """Crash recovery (docs/ROBUSTNESS.md state machine): free every
        slot, re-queue requests with retry budget left (front of queue,
        original submit time), fail the rest terminally as ``error``,
        reallocate the possibly-donated KV buffer, and back off
        exponentially (capped). Returns False when unsupervised or the
        restart budget is spent — the caller escalates to fail_all."""
        if not self.supervise or self.restarts >= self.max_restarts:
            return False
        # graftlock: justified(GL012): single-writer — only the (one) worker/inline step thread recovers
        self.restarts += 1
        self._obs["restarts"].inc()
        logger.warning("engine worker died (%r) — restart %d/%d",
                       exc, self.restarts, self.max_restarts)
        sched, cache = self.scheduler, self.cache
        # reversed: appendleft re-queues LAST-iterated first, and slots are
        # assigned lowest-free-first, so reverse slot order restores the
        # requests' original arrival order at the front of the queue
        for slot in reversed(sched.active_slots()):
            cache.free_slot(slot)
            self._requeue_or_fail(sched.slots.pop(slot))
        # ... ahead of which go the sequences that had left their slots: a
        # step that was launched and not read is dropped with the slots
        self._drop_unlanded()
        if self.prefix is not None:
            # reset_kv is about to zero the device pages, so every cached
            # prefix is garbage: drop the tree wholesale (pin intents
            # survive — re-inserted pinned prefixes re-pin) and rebuild
            # from live traffic
            self.prefix.clear()
        if self.spec is not None:
            # the crash may have died mid-donation of the draft KV buffer
            # too; same-shape reallocation keeps the compiled draft fns
            # (zero new_shape across restarts). Retried requests restart
            # from the prompt, so their draft rows re-prefill — recovery
            # stays lossless.
            self._spec_slots.clear()
            self.spec.reset()
        # the crash may have killed a decode step AFTER the donation of
        # cache.kv; same-shape reallocation keeps the cached jit fns (and
        # therefore the ledger's zero-new_shape property) intact
        cache.reset_kv()
        # cold-start restore: an in-process recovery keeps its compiled
        # fns (every slot non-None — no-op), but a recovery driven from a
        # FRESH process with a populated $DL4J_TPU_COMPILE_CACHE refills
        # any empty slot from the export cache instead of re-jitting
        from deeplearning4j_tpu.serving import aot as _aot

        _aot.maybe_warm_boot(self)
        observe.log_event("engine_restart", restart=self.restarts,
                          error=repr(exc))
        delay = min(self.max_backoff_s,
                    self.restart_backoff_s * (2 ** (self.restarts - 1)))
        if delay > 0:
            time.sleep(delay)
        return True

    # ---------------------------------------------------------- prefix cache
    def _match_prefix(self, req: GenerationRequest) -> Optional[PrefixMatch]:
        """Longest usable cached prefix for an arrival: present, at least
        ``min_match`` tokens, and with an uncached tail that fits the
        compiled suffix bucket (otherwise the full prefill is the only
        compile-once path — match() neither returns nor LRU-refreshes
        such entries). Lookup counting happens in _admit_pages, once per
        admission, so pool-pressure retries don't deflate the hit rate."""
        if self.prefix is None:
            return None
        return self.prefix.match(req.prompt, max_suffix=self.suffix_bucket)

    def _admit_pages(self, slot: int, req: GenerationRequest,
                     match: Optional[PrefixMatch]) -> tuple:
        """Build ``slot``'s page run for ``req`` (``prompt + 1`` tokens).
        Without a match this is plain ``ensure_capacity``. With one: map
        the shared full pages (taking references), copy-on-write the
        partially-filled tail page the prompt diverges in, then allocate
        the rest fresh — evicting unpinned tree leaves first when the
        free list cannot cover it. Any failure (including injected
        ``page_oom`` mid-match) unwinds the slot completely and returns a
        terminal status; the caller completes the request. Returns
        ``(status, prefix_hit_tokens)``."""
        cache = self.cache
        p_len = int(req.prompt.size)
        if self.prefix is not None:
            self.prefix.note_lookup()
        if match is None:
            return cache.ensure_capacity(slot, p_len + 1), 0
        full = match.matched // cache.page_size
        tail_len = match.matched % cache.page_size
        for page in match.pages[:full]:
            cache.map_shared(slot, page)
        if faults.should_fire("page_oom"):
            # injected pool pressure MID-MATCH: unwind the shared
            # mappings (references only — the tree keeps its pages) and
            # report the same terminal oom the real arm would
            cache.free_slot(slot)
            return "oom", 0
        guard = None
        try:
            if tail_len:
                # guard the CoW source FIRST: the pool-pressure eviction
                # below may otherwise drop the tree's (only) reference on
                # it before we copy
                guard = match.pages[full]
                cache.retain(guard)
            need_rest = cache.pages_for(p_len + 1) - full
            if need_rest > cache.free_pages:
                self.prefix.evict_to_free(need_rest - cache.free_pages)
            if tail_len:
                if cache.cow_page(slot, guard) is None:
                    cache.free_slot(slot)
                    return "oom", 0
                self.prefix.note_cow()
            status = cache.ensure_capacity(slot, p_len + 1)
        finally:
            if guard is not None:
                cache.release(guard)
        if status != "ok":
            cache.free_slot(slot)
            return status, 0
        self.prefix.note_hit(match)
        return "ok", match.matched

    def prewarm_prefix(self, prompt, *, pin: bool = True):
        """Run ``prompt`` through one 1-token generation so its KV pages
        land in the prefix tree, then (by default) PIN them — pre-warmed
        per-class system prompts are never evicted (the SLO frontend's
        ``ClassPolicy.shared_prefix`` knob calls this). Works on both an
        idle engine (inline) and a running one (through the queue)."""
        if self.prefix is None:
            raise RuntimeError("prefix cache disabled — construct the "
                               "engine with prefix_pages > 0")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < self.prefix.min_match:
            # a prefix shorter than min_match can never match — pinning
            # it would hold pages forever for zero hits
            logger.warning(
                "shared prefix of %d tokens is below the prefix cache's "
                "min_match=%d — it will never produce a hit (use a longer "
                "prefix or lower prefix_min_match)", prompt.size,
                self.prefix.min_match)
        # graftlock: justified(GL012): advisory mode check — serving mode does not flip mid-prewarm
        if self._worker is None:
            res = self.generate([prompt], max_new_tokens=1, eos_token=-1)[0]
        else:
            res = self.submit(prompt, max_new_tokens=1,
                              eos_token=-1).result(timeout=600)
        if res.finish_reason not in ("eos", "length"):
            logger.warning("prefix pre-warm retired as %r — prefix not "
                           "cached", res.finish_reason)
            return res
        if pin:
            self.prefix.pin(prompt)
        return res

    def check_invariants(self) -> None:
        """Allocator + prefix-tree soundness with EXACT refcount
        accounting, plus draft/target length agreement when speculative
        decoding is on (test/chaos hook)."""
        if self.prefix is not None:
            self.prefix.check_invariants()
            self.cache.check_invariants(tree_refs=self.prefix.page_refs())
        else:
            self.cache.check_invariants()
        sched = self.scheduler
        # (sequence, the tokens a launch waiting to be read yields it)
        waited = []
        if self._flying is not None:
            ys = self._flying.yields or [(0, 1)] * len(self._flying.slots)
            waited = [(st, n) for (_slot, st), (_f, n)
                      in zip(self._flying.slots, ys)]
        waited += [(adm.st, int(adm.tok is not None))
                   for adm in self._landing]
        for st in list(sched.slots.values()) + sched.leaving:
            mine = [n for w, n in waited if w is st and n]
            assert st.unlanded == sum(mine) and len(mine) <= 1, (
                f"request {st.request.request_id}: {st.unlanded} tokens "
                f"counted unlanded, {len(mine)} launches waiting to be read")
        for st in sched.leaving:
            assert st.unlanded >= 1 and len(st.tokens) + st.unlanded >= \
                st.request.max_new_tokens, (
                    f"request {st.request.request_id} left its slot with "
                    f"{len(st.tokens)} tokens and {st.unlanded} in flight")
        if self.spec is not None:
            assert self._spec_slots <= set(self.scheduler.slots), (
                f"speculating slots {self._spec_slots} outside the active "
                f"bank {sorted(self.scheduler.slots)}")
            self.spec.check_invariants(self._spec_slots, self.cache.seq_lens)

    # ------------------------------------------------------------ scheduling
    def _first_need(self, prompt_len: int) -> int:
        """Positions an admission needs room for: the prompt and the first
        token, which the same iteration's decode caches; a block model's
        prompt's whole blocks and its first block, to the block's end."""
        b = self.block
        return prompt_len + 1 if b == 1 else (prompt_len // b + 1) * b

    def _free_slot(self, slot: int, completed: bool) -> None:
        """Give back what ``slot`` holds beside its scheduler state: its
        pages and its draft row."""
        if self.prefix is not None and completed:
            # a COMPLETED sequence donates its prompt's pages to the
            # radix tree (insert or LRU-refresh) before the slot lets go
            st = self.scheduler.slots[slot]
            n = self.cache.pages_for(st.prompt_len)
            self.prefix.insert(st.request.prompt,
                               list(self.cache.owned[slot][:n]))
        self.cache.free_slot(slot)
        if self.spec is not None:
            self._spec_slots.discard(slot)
            self.spec.free(slot)

    def _retire(self, slot: int, reason: str) -> None:
        self._free_slot(slot, reason in ("eos", "length"))
        self.scheduler.retire(slot, reason)
        count_terminal(reason)

    def _retire_or_hold(self, slot: int, reason: str, hold: set) -> None:
        """Retire ``slot`` for a reason that is not its own completion
        (``deadline``, ``overflow``, ``oom``), unless a token of its is
        still in flight: that token may be the one that completes it, so
        the slot sits out this launch and the question is asked again once
        the token has landed."""
        if self.scheduler.slots[slot].unlanded:
            hold.add(slot)
        else:
            self._retire(slot, reason)

    def _finish_complete(self) -> None:
        """Retire the sequences that their landed tokens complete; one that
        is complete by its count, its last token in flight, frees its slot
        and pages now and gets its result when that token lands
        (:meth:`_finish_leaving`). The device runs programs in launch order
        over the one donated pool, so a prefill into those pages is queued
        behind the decode step that last used them."""
        sched = self.scheduler
        for slot in sched.active_slots():
            reason = sched.should_finish(slot)
            if reason:
                self._retire(slot, reason)
            elif sched.last_in_flight(slot):
                self._free_slot(slot, True)
                sched.detach(slot)

    def _finish_leaving(self, st) -> None:
        """A token landed for ``st``: if it had left its slot, that was its
        last and its result is complete."""
        sched = self.scheduler
        if any(s is st for s in sched.leaving):
            reason = sched.finish_reason(st)
            sched.finish(st, reason)
            count_terminal(reason)

    def step(self) -> int:
        """ONE scheduler iteration: capacity-evict, admit, retire finished,
        then one decode step for the whole slot bank, whose tokens are read
        and committed before it returns. Returns the number of tokens
        generated (0 when idle). The whole of it is one ``serving_step``
        span whose children are the stages (docs/OBSERVABILITY.md § Span
        catalogue)."""
        return self._iterate(ahead=False)

    def _iterate(self, ahead: bool) -> int:
        """One iteration of the loop, in one of two orders of the same
        halves (docs/SERVING.md § The loop). Inline (:meth:`step`) it reads
        every token where it was launched. The worker runs it ``ahead``:
        the decode step it launches stays in flight, and what it reads is
        the step before (then this iteration's admissions, in launch
        order), so the device always has its next program queued."""
        sched = self.scheduler
        # graftlock: justified(GL012): single-writer — only the (one) worker/inline step thread steps
        self._step_count += 1
        with observe.tracer().span(
                "serving_step", category="serving", host=True,
                step=self._step_count, pending=len(sched.pending),
                active=len(sched.slots)) as sp:
            admitted, produced = self._step(ahead)
            sp.set(admitted=admitted, produced=produced)
        return produced

    def _step(self, ahead: bool) -> tuple:
        """The stages of :meth:`_iterate`; returns (admitted, produced)."""
        cache, sched = self.cache, self.scheduler
        tracer = observe.tracer()
        admitted = 0
        hold: set = set()  # slots that sit this launch out

        with tracer.span("serving_schedule", category="serving") as stage:
            held = len(sched.slots)
            # 1. retire sequences completed by the previous iteration FIRST:
            #    a finished slot must neither grab capacity pages it will never
            #    write nor be mis-retired as oom/overflow (which would skip the
            #    eos trim and steal pages a live neighbour needed)
            self._finish_complete()

            # 1b. deadlines — AFTER completion so a finished sequence keeps its
            #     honest eos/length reason; overdue work retires as "deadline"
            #     (active: partial tokens; queued: empty result, no slot taken)
            now = time.perf_counter()
            for slot in sched.active_slots():
                dl = sched.slots[slot].request.deadline_s
                if dl is not None and now - sched.slots[slot].submit_t > dl:
                    self._retire_or_hold(slot, "deadline", hold)
            expired = []
            with sched._plock:
                for _ in range(len(sched.pending)):
                    item = sched.pending.popleft()
                    if (item[0].deadline_s is not None
                            and now - item[2] > item[0].deadline_s):
                        expired.append(item)
                    else:
                        sched.pending.append(item)
            for req, fut, t_sub in expired:  # complete OUTSIDE the queue lock
                # — future callbacks (frontend accounting) must not run under it
                self._finish_unslotted(req, fut, "deadline", t_sub)

            # 2. capacity: every surviving slot needs room for one more token
            #    (a block model's: for the block, to its end)
            for slot in sched.active_slots():
                need = int(cache.seq_lens[slot]) + self.block
                if need > self.cfg.max_position:
                    self._retire_or_hold(slot, "overflow", hold)
                    continue
                status = cache.ensure_capacity(slot, need)
                if status != "ok":
                    self._retire_or_hold(slot, status, hold)
            stage.set(retired=held - len(sched.slots) + len(expired))

        # 3. admissions into free slots, highest-priority first (FIFO
        #    within a priority — peek_best_pending orders by (priority,
        #    submit time), so supervisor retries with their ORIGINAL
        #    submit time re-admit ahead of younger same-class work and
        #    recovery never inverts priority). submit() already bounds
        #    prompts to the max_prompt bucket, which __init__ bounds to
        #    the per-slot context — no per-request overflow check here.
        while True:
            free = sched.free_slot_ids()
            if not free:
                break
            item = sched.peek_best_pending()
            if item is None:
                break
            req, fut, t_sub = item
            with tracer.span("serving_admit", category="serving",
                             request=req.request_id, slot=free[0],
                             admitted=False) as adm:
                p_len = int(req.prompt.size)
                # p_len + 1 everywhere: the SAME iteration's decode writes the
                # first generated token's K/V at position p_len, so a page-
                # aligned prompt needs its next page NOW — allocating only the
                # prompt's pages would send that write to the trash page.
                # A prefix-cache match discounts its shared full pages from
                # the bill (the CoW tail still costs a fresh page), and the
                # tree's unpinned pages count as reclaimable supply.
                match = self._match_prefix(req)
                need_new = cache.pages_for(self._first_need(p_len)) - (
                    match.matched // cache.page_size if match else 0)
                if need_new > cache.free_pages:
                    # only now pay the O(tree) reclaimable walk: tree pages
                    # eviction would ACTUALLY free (no slot holders, and not
                    # the match's own pages — those are being consumed, not
                    # freed) count as supply — overcounting here would turn
                    # this wait into a spurious terminal oom downstream
                    reclaimable = (self.prefix.reclaimable_pages(
                        exclude=match.pages if match else ())
                        if self.prefix is not None else 0)
                    if need_new > cache.free_pages + reclaimable:
                        if not sched.slots:
                            # nothing active to ever free pages —
                            # config-impossible
                            if sched.remove_pending(item) and not fut.done():
                                fut.set_exception(RuntimeError(
                                    f"prompt needs {need_new} free pages but "
                                    f"the pool only has {cache.num_pages} "
                                    f"({reclaimable} reclaimable from the "
                                    f"prefix tree)"))
                                count_terminal("error")
                                note_terminal(req, t_sub, "error")
                            continue
                        break  # pool pressure: wait for evictions
                if not sched.remove_pending(item):
                    continue  # a frontend steal raced us — re-select
                slot = free[0]
                try:
                    status, hit_tokens = self._admit_pages(slot, req, match)
                except BaseException:
                    # same unwind as the prefill crash below: admission may
                    # have mapped shared pages / grown the slot before dying
                    # (eviction callback, allocator fault) — release whatever
                    # the slot holds and put the request back at the queue
                    # FRONT so supervision retries it instead of leaking the
                    # pages and stranding the future
                    cache.free_slot(slot)
                    with sched._plock:
                        sched.pending.appendleft(item)
                    raise
                if status != "ok":
                    # the free-pages precheck passed, so this is injected pool
                    # pressure (faults.page_oom) or an allocator race: complete
                    # the request terminally instead of prefilling into a
                    # trash-page-only row (which would corrupt the invariants)
                    self._finish_unslotted(req, fut, status, t_sub)
                    continue
                self._slot_match[slot] = match if hit_tokens else None
                try:
                    launched = self._prefill_into(slot, req)
                except BaseException:
                    # the request sits in neither pending nor a slot right
                    # now — put it back at the queue FRONT (original submit
                    # time) and release the just-grown pages, so supervision
                    # retries it instead of stranding its future forever
                    cache.free_slot(slot)
                    with sched._plock:
                        sched.pending.appendleft(item)
                    raise
                # a block model's prefill caches the prompt's whole blocks
                # and launches no token
                cache.seq_lens[slot] = p_len // self.block * self.block
                st = sched.admit(slot, req, fut, t_sub,
                                 prefix_hit_tokens=hit_tokens,
                                 unlanded=int(self.block == 1))
                self._landing.append(_Admission(st, adm.id, *launched))
                if not ahead:
                    self._land_admissions()
                self._obs["admitted"].inc()
                # the wait in the queue: submit -> the start of the admission
                # that took the request (a retried request waits twice)
                tracer.async_between(
                    "serving_queue_wait", t_sub, adm.start,
                    key=req.request_id, category="serving",
                    request=req.request_id, priority=req.priority)
                self._obs["queue_wait_h"].observe(adm.start - t_sub)
                adm.set(admitted=True)
                admitted += 1
                if (self.spec is not None and req.temperature <= 0.0
                        and not req.spec_disabled):
                    # greedy slots speculate: the draft prefills the SAME
                    # prompt (full — the draft cache has no prefix tree) so
                    # draft and target agree on a cached length of p_len.
                    # Sampling (temperature > 0) and spec_disabled requests
                    # stay on the plain decode path. A crash in here is
                    # supervised like any admission crash: the request
                    # already holds its slot, so _recover re-queues it.
                    self.spec.prefill(slot, req.prompt)
                    self._spec_slots.add(slot)

        with tracer.span("serving_schedule", category="serving") as stage:
            held = len(sched.slots)
            # 4. a just-admitted sequence can already be done (first token was
            #    its eos, or max_new_tokens == 1) — retire before decoding
            self._finish_complete()
            stage.set(retired=held - len(sched.slots))

        self._obs["occupancy"].set(sched.occupancy())
        active = [slot for slot in sched.active_slots() if slot not in hold]
        if not active:
            # nothing to launch: what is in flight lands
            return admitted, self._land()

        # 5. one decode iteration over the whole slot bank. With
        #    speculation on, the bank splits: slots that can take a
        #    spec_k+1-token verify window this step go the draft+verify
        #    path; everything else (sampling slots, spec-disabled
        #    requests, sequences near their context/position limit) rides
        #    the plain one-token decode. Both dispatches keep config-only
        #    shapes, so a mixed bank still never recompiles.
        spec_now: List[int] = []
        plain: List[int] = []
        for slot in active:
            if self.spec is not None and slot in self._spec_slots:
                need = int(cache.seq_lens[slot]) + self.spec.k + 1
                if (need <= self._spec_limit
                        and cache.pages_for(need) <= cache.max_pages_per_seq
                        and cache.ensure_capacity(slot, need) == "ok"):
                    spec_now.append(slot)
                    continue
                # a slot that cannot host the verify window finishes its
                # sequence NON-speculatively: one plain step would advance
                # the target past the draft cache (length drift), so the
                # draft row is abandoned rather than resynced. The verify
                # passes committed its tokens on the host: the newest joins
                # the bank's vector for the plain steps that follow
                self._spec_slots.discard(slot)
                self.spec.free(slot)
                toks = np.array(self._toks)
                toks[slot] = sched.slots[slot].tokens[-1]
                self._toks = toks
            plain.append(slot)

        # chaos hooks (docs/ROBUSTNESS.md): both fire BEFORE any dispatch
        # so an injected crash never leaves a donated kv buffer half
        # consumed inside a real XLA call; _step_speculative arms a
        # second decode_step_error point between draft and verify (the
        # mid-speculation state the chaos leg drives)
        faults.maybe_fail("decode_step_error")
        faults.maybe_sleep("slow_decode", 0.05)

        produced = 0
        if plain and self.block > 1:
            produced += self._step_block(plain, ahead)
        elif plain:
            produced += self._step_decode(plain, ahead)
        if spec_now:
            produced += self._step_speculative(spec_now)
        if self._landing:
            # the worker's loop: this iteration's admissions, read behind
            # the decode step's launch
            with tracer.span("serving_first_tokens", category="serving"):
                self._land_admissions()
        return admitted, produced

    def _step_decode(self, active: List[int], ahead: bool) -> int:
        """The plain one-token decode iteration over ``active`` (the
        whole bank when speculation is off): launch this step, then read
        and commit the step that is due — the one before where the loop
        runs ``ahead`` (this one stays in flight), else this one."""
        cache, sched = self.cache, self.scheduler
        tracer = observe.tracer()
        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        key = self._next_key()
        with tracer.span("serving_decode_upload", category="serving"):
            s_n = cache.max_slots
            act = np.zeros((s_n,), np.int32)
            temp = np.zeros((s_n,), np.float32)
            top_k = np.zeros((s_n,), np.int32)
            top_p = np.ones((s_n,), np.float32)
            for slot in active:
                st = sched.slots[slot]
                act[slot] = 1
                temp[slot] = st.request.temperature
                top_k[slot] = st.request.top_k
                top_p[slot] = st.request.top_p
            # what changes every step goes to the jitted call as host
            # arrays: its own transfer of an argument costs half of a
            # ``jnp.asarray`` (0.14 against 0.3 ms on the v5e's host).
            # Copies, because the cache updates its tables in place. What
            # seldom changes stays on the device, and the tokens never
            # leave it: the bank's vector is the launches' own result
            args = (*cache.decode_args(), self._toks,
                    self._resident("active", act))
            sampling = (self._resident("temperature", temp),
                        self._resident("top_k", top_k),
                        self._resident("top_p", top_p))
            if any(a is not b for a, b in zip(sampling, self._sampler[0])):
                # the device's own predicate, on the values it was sent
                self._sampler = (sampling, SAMPLER_PATHS[
                    int(sampler_path(temp, top_k, top_p))])
            observe.note_jit_signature(
                self._decode_fn, graph="serving", key="decode",
                signature=observe.signature_of(
                    page_table=getattr(cache, "page_table", None),
                    seq_lens=cache.seq_lens, tokens=self._toks, active=act))
            attn = {}
            if getattr(cache, "sides", 0) == 2:
                # K/V rows: the groups of pages the paged kernel walks, over
                # the lengths this step's token makes
                attn["attn_groups"] = paged_decode_groups(
                    cache.seq_lens[active] + 1, cache.page_size)
                self._obs["paged_groups"].inc(attn["attn_groups"])
        # None in a synchronous iteration: it reads what it launches
        before = self._flying
        t0 = time.perf_counter()
        with tracer.span("serving_decode", category="serving",
                         slots=len(active), sampler=self._sampler[1],
                         ahead=int(before is not None), **attn) as sp:
            with tracer.span("serving_decode_launch", category="serving"):
                cache.kv, self._toks, _logits, stats = self._decode_fn(
                    self.model.params, cache.kv, *args, key, *sampling)
            step = _DecodeStep([(slot, sched.slots[slot]) for slot in active],
                               self._toks, stats, sp)
            for slot, st in step.slots:
                # known without the token's value: the fed token is cached
                # by the time any later program runs
                cache.seq_lens[slot] += 1
                st.unlanded += 1
            self._flying = step if ahead else None
            due = before if ahead else step
            self._reserve_keys()
            toks = self._read_decode(due) if due is not None else None
        dt = time.perf_counter() - t0
        self._obs["decode_h"].observe(dt)
        self._obs["sampler"][self._sampler[1]].inc()
        self._obs["launches"][int(before is not None)].inc()
        return self._commit_decode(due, toks) if due is not None else 0

    def _read_decode(self, step: _DecodeStep) -> np.ndarray:
        """The blocking read of a launched decode step: its tokens and the
        model's statistics in ONE transfer. The statistics go to the step's
        own ``serving_decode`` span, open or closed."""
        with observe.tracer().span("serving_decode_read",
                                   category="serving", host=True):
            toks, stats = jax.device_get((step.toks, step.stats))
        if stats is not None:
            self.programs.note_stats(stats, step.span, decode_step=True,
                                     tokens=len(toks))
        return toks

    def _commit_decode(self, step: _DecodeStep, toks: np.ndarray) -> int:
        """Hand a read step's tokens to their sequences, stamped now: when
        they reached the host is what a caller can see. A sequence that is
        gone, or complete by what landed before (its ``eos`` was read a
        step late), takes none: that token is dropped, its K/V row lies
        past the length the result keeps."""
        sched = self.scheduler
        with observe.tracer().span("serving_commit", category="serving"):
            now = time.perf_counter()
            produced = 0
            for slot, st in step.slots:
                if not sched.holds(slot, st) or sched.finish_reason(st):
                    st.unlanded -= 1
                    continue
                if st.last_token_t is not None:
                    self._obs["itl_h"].observe(now - st.last_token_t)
                sched.on_decode_token(st, int(toks[slot]), now)
                produced += 1
                self._finish_leaving(st)
            self._obs["generated"].inc(produced)
        return produced

    def _step_block(self, active: List[int], ahead: bool) -> int:
        """A block model's iteration over ``active``: launch one block for
        each (the bank's ``_block_init``: the mask token, or a first block's
        fixed prompt tail), then read and commit the step that is due, as
        :meth:`_step_decode` does. A slot's cached length advances by the
        block at the launch, and it counts the tokens its block yields as
        unlanded until they land."""
        cache, sched = self.cache, self.scheduler
        tracer = observe.tracer()
        b, m_id = self.block, self.cfg.mask_token_id
        if self._decode_fn is None:
            self._decode_fn = self._build_decode()
        with tracer.span("serving_decode_upload", category="serving"):
            act = np.zeros((cache.max_slots,), np.int32)
            act[active] = 1
            init = self._block_init.copy()
            args = (*cache.decode_args(), self._resident("block_init", init),
                    self._resident("active", act))
            observe.note_jit_signature(
                self._decode_fn, graph="serving", key="decode",
                signature=observe.signature_of(
                    page_table=cache.page_table, seq_lens=cache.seq_lens,
                    block_init=init, active=act))
        before = self._flying
        t0 = time.perf_counter()
        with tracer.span("serving_decode", category="serving",
                         slots=len(active),
                         ahead=int(before is not None)) as sp:
            with tracer.span("serving_decode_launch", category="serving"):
                cache.kv, toks, when, passes, stats = self._decode_fn(
                    self.model.params, cache.kv, *args)
            fixed = (init[active] != m_id).sum(axis=1).tolist()
            step = _DecodeStep(
                [(slot, sched.slots[slot]) for slot in active], toks, stats,
                sp, when=when, passes=passes,
                yields=[(f, b - f) for f in fixed])
            for (slot, st), (_f, n) in zip(step.slots, step.yields):
                cache.seq_lens[slot] += b
                st.unlanded += n
            # a first block's prompt tail went with this launch
            self._block_init[active] = m_id
            self._flying = step if ahead else None
            due = before if ahead else step
            read = self._read_block(due) if due is not None else None
        self._obs["decode_h"].observe(time.perf_counter() - t0)
        self._obs["launches"][int(before is not None)].inc()
        return self._commit_block(due, *read) if due is not None else 0

    def _read_block(self, step: _DecodeStep) -> tuple:
        """The blocking read of a launched block step: its tokens, the pass
        that unmasked each, the passes run and the model's statistics in ONE
        transfer; the statistics and the step's diffusion arguments go to
        its own ``serving_decode`` span, open or closed."""
        with observe.tracer().span("serving_decode_read",
                                   category="serving", host=True):
            toks, when, passes, stats = jax.device_get(
                (step.toks, step.when, step.passes, step.stats))
        if stats is not None:
            self.programs.note_stats(stats, step.span, decode_step=True,
                                     tokens=toks.size)
        masked = sum(n for _f, n in step.yields)
        observe.note_diffusion(passes, masked, masked, len(step.slots),
                               step.span)
        return toks, when

    def _commit_block(self, step: _DecodeStep, toks: np.ndarray,
                      when: np.ndarray) -> int:
        """Hand a read block step's tokens to their sequences, stamped
        together now: a slot's masked positions in order, cut after an
        ``eos`` and at ``max_new_tokens``. What is cut is dropped (its rows
        lie past the length the result keeps), as is the whole block of a
        sequence that is gone or complete by what landed before."""
        sched = self.scheduler
        with observe.tracer().span("serving_commit", category="serving"):
            now = time.perf_counter()
            produced = 0
            for (slot, st), (fixed, n) in zip(step.slots, step.yields):
                if not sched.holds(slot, st) or sched.finish_reason(st):
                    st.unlanded -= n
                    continue
                keep = [int(t) for t in toks[slot, fixed:]]
                keep = keep[:st.request.max_new_tokens - len(st.tokens)]
                if st.request.eos_token in keep:
                    keep = keep[:keep.index(st.request.eos_token) + 1]
                first = st.ttft_s is None
                gaps = sched.on_block_tokens(
                    st, keep, [int(p) for p in when[slot, fixed:]], now, n)
                if first and keep:
                    self._obs["ttft_h"].observe(now - st.submit_t)
                for gap in gaps:
                    self._obs["itl_h"].observe(gap)
                produced += len(keep)
                self._finish_leaving(st)
            self._obs["generated"].inc(produced)
        return produced

    def _land(self) -> int:
        """Read and commit everything launched and not read yet: the decode
        step in flight, then the admissions, in launch order. Returns the
        decode tokens committed."""
        step, self._flying = self._flying, None
        produced = 0
        if step is not None and step.yields is not None:
            produced = self._commit_block(step, *self._read_block(step))
        elif step is not None:
            produced = self._commit_decode(step, self._read_decode(step))
        self._land_admissions()
        return produced

    def _land_admissions(self) -> None:
        """Read the first token of every launched admission, in launch
        order, each stamped when its own read returns: a request's first
        token does not wait for the admissions behind it. This is where
        ``serving_prefill`` ends: it runs from the prefill's launch to its
        token on the host, whatever the loop launched in between."""
        sched, tracer = self.scheduler, observe.tracer()
        while self._landing:
            adm = self._landing.pop(0)
            t0 = time.perf_counter()
            since = host_usage()
            tok, stats = jax.device_get((adm.tok, adm.stats))
            used = host_since(since)
            now = time.perf_counter()
            # never opened: it collects the args of a span whose extent is
            # known only now
            sp = tracer.span("serving_prefill", category="serving",
                             **adm.args)
            if stats is not None:
                self.programs.note_stats(stats, sp, tokens=self.max_prompt)
            pid = tracer.complete_between(
                sp.name, adm.launch[0], now, category=sp.category,
                parent=adm.parent, **sp.args)
            tracer.complete_between("serving_prefill_launch", *adm.launch,
                                    category="serving", parent=pid)
            tracer.complete_between("serving_prefill_read", t0, now,
                                    category="serving", parent=pid, **used)
            if tok is None:
                # a block model's admission launched no token
                continue
            sched.on_first_token(adm.st, int(tok), now)
            self._obs["generated"].inc()
            self._obs["ttft_h"].observe(now - adm.st.submit_t)
            self._finish_leaving(adm.st)

    def _step_speculative(self, spec_now: List[int]) -> int:
        """One speculative iteration for ``spec_now`` (docs/SERVING.md
        § Speculative decoding): K draft proposals per slot (one compiled
        scan), ONE target verify pass over the K+1-token window, then
        greedy exact-match acceptance on the host — commit the agreed
        draft prefix plus the target's correction/bonus token, REWIND the
        cached lengths past it (rejected positions become garbage beyond
        the length: never read, refcount-untouched, overwritten next
        pass). Capacity for the full window was reserved by the caller.

        Latency accounting is per COMMITTED token: a step that lands m
        tokens contributes m observations of (step/m) to the decode and
        inter-token histograms, so spec-on percentiles — and the SLO
        frontend's rolling decode-p50 built on the decode histogram —
        price a token, not a step, and stay comparable to spec-off.
        """
        spec, cache, sched = self.spec, self.cache, self.scheduler
        s_n = cache.max_slots
        pend = np.zeros((s_n,), np.int32)
        act = np.zeros((s_n,), np.int32)
        for slot in spec_now:
            pend[slot] = sched.slots[slot].tokens[-1]
            act[slot] = 1
        t0 = time.perf_counter()
        props = spec.propose(pend, act)          # (S, K) — draft phase
        # second decode_step_error arm, MID-speculation: the draft KV was
        # just donated-and-advanced but nothing committed — the exact
        # state SpeculativeDecoder.reset() exists for; still outside any
        # XLA call, so no buffer is ever half consumed (chaos-leg-driven)
        faults.maybe_fail("decode_step_error")
        vtokens = np.zeros((s_n, spec.k + 1), np.int32)
        vtokens[:, 0] = pend
        vtokens[:, 1:] = props
        if self._verify_fn is None:
            self._verify_fn = self._build_verify()
        observe.note_jit_signature(
            self._verify_fn, graph="serving", key="verify",
            signature=observe.signature_of(
                tokens=vtokens, seq_lens=cache.seq_lens,
                page_table=cache.page_table, active=act))
        with observe.tracer().span("serving_verify", category="serving",
                                   slots=len(spec_now)):
            cache.kv, greedy = self._verify_fn(
                self.model.params, cache.kv, jnp.asarray(vtokens),
                jnp.asarray(cache.seq_lens),
                jnp.asarray(cache.page_table), jnp.asarray(act))
            greedy = np.asarray(greedy)          # (S, K+1) target argmax
        dt = time.perf_counter() - t0
        now = time.perf_counter()
        committed_total = 0
        accepted_total = 0
        for slot in spec_now:
            st = sched.slots[slot]
            # greedy exact-match acceptance: proposal i is accepted iff
            # it equals the target's argmax after the previous token
            j = 0
            while j < spec.k and props[slot, j] == greedy[slot, j]:
                j += 1
            toks = [int(t) for t in props[slot, :j]]
            toks.append(int(greedy[slot, j]))    # correction / bonus
            # truncation: never exceed the request's remaining budget,
            # and never commit past an eos (retire trims the eos itself)
            rem = st.request.max_new_tokens - len(st.tokens)
            toks = toks[:max(1, rem)]
            eos = st.request.eos_token
            for i, t in enumerate(toks):
                if t == eos:
                    toks = toks[:i + 1]
                    break
            m = len(toks)
            # the rewind: t0 and the first m-1 commits are cached (their
            # K/V was written at seq_lens..seq_lens+m-1); the LAST commit
            # is the next step's feed, and positions seq_lens+m.. hold
            # rejected garbage beyond the length
            cache.seq_lens[slot] += m
            spec.commit(slot, m)
            from_draft = min(j, m)               # drafts that landed
            spec.note_outcome(spec.k, j, from_draft)
            gap = sched.on_spec_tokens(slot, toks, now, spec.k, from_draft)
            per_tok = dt / m
            for _ in range(m):
                self._obs["decode_h"].observe(per_tok)
                if gap is not None:
                    self._obs["itl_h"].observe(gap)
            committed_total += m
            accepted_total += from_draft
        self._obs["generated"].inc(committed_total)
        observe.log_event(
            "serving_spec", slots=len(spec_now), proposed=spec.k
            * len(spec_now), accepted=accepted_total,
            committed=committed_total, step_seconds=round(dt, 6))
        return committed_total

    def _prefill_into(self, slot: int, req: GenerationRequest) -> tuple:
        """Launch the (bucketed) prefill and the scatter of its K/V into the
        slot's pages. Returns what :meth:`_land_admissions` reads later, the
        tail of an :class:`_Admission`: the sampled token and the model's
        statistics (both still on the device), the launch's two clock
        readings and ``serving_prefill``'s args. With a prefix-cache match
        staged for this slot the shared pages are already mapped and only
        the SUFFIX runs — TTFT is measured across this (much shorter)
        pass."""
        match = self._slot_match.pop(slot, None)
        if match is not None:
            return self._prefill_suffix_into(slot, req, match)
        if self.block > 1:
            return self._prefill_block_into(slot, req)
        cache = self.cache
        p_len = int(req.prompt.size)
        ids = np.zeros((1, self.max_prompt), np.int32)
        ids[0, :p_len] = req.prompt
        if self._prefill_fn is None:
            self._prefill_fn = self._build_prefill()
        if self._write_fn is None:
            self._write_fn = self._build_write()
        key = self._next_key()
        observe.note_jit_signature(
            self._prefill_fn, graph="serving", key="prefill",
            signature=observe.signature_of(ids=ids))
        observe.note_jit_signature(
            self._write_fn, graph="serving", key="write_prompt",
            signature=observe.signature_of(ids=ids))
        t0 = time.perf_counter()
        kv_prompt, tok, stats, self._toks = self._prefill_fn(
            self.model.params, ids, np.int32(p_len), key,
            self._resident("prefill_temperature", np.asarray(
                [req.temperature], np.float32)),
            self._resident("prefill_top_k", np.asarray(
                [req.top_k], np.int32)),
            self._resident("prefill_top_p", np.asarray(
                [req.top_p], np.float32)),
            self._toks, np.int32(slot))
        cache.kv = self._write_fn(cache.kv, kv_prompt,
                                  *cache.write_args(slot, p_len))
        return (tok, stats, (t0, time.perf_counter()),
                dict(prompt_len=p_len, request=req.request_id))

    def _prefill_block_into(self, slot: int, req: GenerationRequest) -> tuple:
        """A block model's admission: launch the forward over the prompt's
        whole blocks and the write of their rows into the slot's pages, and
        set the slot's first block in the bank (the prompt's tail, fixed,
        then the mask token). No token is launched: the first block's
        tokens come from the next decode step."""
        cache, b = self.cache, self.block
        p_len = int(req.prompt.size)
        whole = p_len // b * b
        ids = np.zeros((1, self.max_prompt), np.int32)
        ids[0, :p_len] = req.prompt
        if self._prefill_fn is None:
            self._prefill_fn = self._build_prefill()
        if self._write_fn is None:
            self._write_fn = self._build_write()
        observe.note_jit_signature(
            self._prefill_fn, graph="serving", key="prefill",
            signature=observe.signature_of(ids=ids))
        observe.note_jit_signature(
            self._write_fn, graph="serving", key="write_prompt",
            signature=observe.signature_of(ids=ids))
        t0 = time.perf_counter()
        rows, stats = self._prefill_fn(self.model.params, ids,
                                       np.int32(whole))
        cache.kv = self._write_fn(cache.kv, rows,
                                  *cache.write_args(slot, whole))
        self._block_init[slot] = self.cfg.mask_token_id
        self._block_init[slot, :p_len - whole] = req.prompt[whole:]
        return (None, stats, (t0, time.perf_counter()),
                dict(prompt_len=p_len, request=req.request_id, dd_passes=1,
                     dd_masked=0, dd_tokens=0))

    def _prefill_suffix_into(self, slot: int, req: GenerationRequest,
                             match: PrefixMatch) -> tuple:
        """Prefix-hit admission: prefill ONLY the uncached suffix against
        the cached prefix pages already mapped into the slot's row."""
        cache = self.cache
        p_len = int(req.prompt.size)
        suffix = np.asarray(req.prompt).reshape(-1)[match.matched:]
        ids = np.zeros((1, self.suffix_bucket), np.int32)
        ids[0, :suffix.size] = suffix
        if self._suffix_fn is None:
            self._suffix_fn = self._build_suffix()
        key = self._next_key()
        observe.note_jit_signature(
            self._suffix_fn, graph="serving", key="suffix_prefill",
            signature=observe.signature_of(ids=ids))
        t0 = time.perf_counter()
        cache.kv, tok, self._toks = self._suffix_fn(
            self.model.params, cache.kv, jnp.asarray(ids),
            jnp.asarray(match.matched, jnp.int32),
            jnp.asarray(suffix.size, jnp.int32),
            jnp.asarray(cache.page_table[slot]), key,
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.top_p], jnp.float32),
            self._toks, np.int32(slot))
        return (tok, None, (t0, time.perf_counter()),
                dict(prompt_len=p_len, prefix_hit=match.matched,
                     request=req.request_id))
