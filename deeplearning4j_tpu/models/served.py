"""What the serving engine asks a model for (docs/SERVING.md § The model
protocol). ``serving.GenerativeEngine`` knows no model by name: any handle
with ``cfg`` (``vocab_size``, ``eos_token``, ``max_position``), ``params``,
``cache_rows()`` and ``serving_programs()`` is served through the same
scheduler and sampler. ``cache_rows()`` says what the model keeps between
steps, in one of two geometries: rows a TOKEN in pages (:class:`CacheRows`:
attention over a context) or a fixed-size state a SLOT (:class:`SlotState`:
a recurrence). The engine builds its pool, its write and its decode program
from the geometry it is given. Its programs say how many tokens a step
yields a sequence: one, or a BLOCK (``ServingPrograms.block``: a model that
generates by diffusion over blocks)."""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple


class CacheRows(NamedTuple):
    """The geometry of what a token leaves in the paged cache: ``layers``
    attention (sub-)layers, each with ``sides`` rows a token (2: a key row
    and a value row; 1: one latent row) of ``width`` values. The pool is
    ``(layers, sides, pages + 1, page_size, width)``."""

    layers: int
    sides: int
    width: int


class SlotState(NamedTuple):
    """The geometry of a model whose cache is a fixed-size state a slot,
    whatever the sequence's length: ``arrays`` names each array of ONE
    slot's state with its ``(shape, dtype)``. The pool
    (``serving.cache.SlotStatePool``) is a dict of the same names, each
    ``(max_slots,) + shape`` in its own dtype."""

    arrays: Dict[str, Tuple[Tuple[int, ...], Any]]


class ServingPrograms(NamedTuple):
    """A model's jittable programs, bound to its configuration.

    With :class:`CacheRows`:

    * ``prefill(params, ids (1, T), prompt_len)`` -> ``(logits (1, V) of the
      last real position, rows (layers, sides, T, width), stats)``.
    * ``decode_step(params, kv_pages, tokens, positions, page_table,
      seq_lens_incl, write_page, write_offset)`` -> ``(kv_pages, logits
      (S, V), stats)``: one token for every slot against the donated pool.

    With :class:`SlotState` (no page table: a slot IS where its state lies):

    * ``prefill(params, ids (1, T), prompt_len)`` -> ``(logits (1, V) of the
      last real position, state: the slot's arrays after the last real
      position, stats)``; padded positions leave no trace in it.
    * ``decode_step(params, pool, tokens, positions, active)`` -> ``(pool,
      logits (S, V), stats)``: one token for every slot against the donated
      pool of states; a slot that is not ``active`` keeps its state to the
      last bit.

    With :class:`CacheRows` and ``block`` = B > 1 (generation by diffusion
    over blocks of ``B`` positions: a step yields a block a sequence; greedy,
    and the model's ``cfg`` names its ``mask_token_id``, which no prompt may
    hold):

    * ``prefill(params, ids (1, T), prompt_len)`` -> ``(rows (layers, sides,
      T, width), stats)``: the forward over the prompt's whole blocks,
      ``prompt_len`` a multiple of ``B`` (the engine gives ``(L // B) * B``);
      no logits: the first tokens come from the first block.
    * ``decode_step(params, kv_pages, block_init (S, B), starts (S,),
      page_table, write_page (S, B), write_offset (S, B), active (S,) bool)``
      -> ``(kv_pages, tokens (S, B), unmask_pass (S, B), passes, stats)``:
      one block for every active slot, the mask token where ``block_init``
      is masked and the prompt's tail where a first block holds one (fixed:
      its ``unmask_pass`` is -1); ``starts`` is the block's first position
      and the rows cached before it. ``passes`` is how many forward passes
      the program ran, the last over the clean blocks, whose rows it writes
      at ``write_page``/``write_offset``; no pass that still holds a mask
      writes the pool.

    Either way:

    * ``prefill_suffix(params, ids, prefix_kv, prefix_len, suffix_len)`` ->
      ``(logits (1, B, V), rows (layers, sides, B, width))``, optional: the
      radix prefix cache needs it (rows a token only).
    * ``verify(params, kv_pages, tokens, seq_lens, page_table, write_pages,
      write_offsets, page_size=)`` -> ``(kv_pages, greedy (S, B))``,
      optional: speculative decoding needs it (rows a token only).

    ``stats`` is ``None`` or a small pytree of device arrays that reaches the
    host in the read the step already makes and is handed, as numpy arrays,
    to ``note_stats(stats, span, decode_step=, tokens=)`` (``tokens``: the
    token rows the program ran over, the bank's slots or the padded prompt's
    positions): the engine never looks inside it, the model that gives
    statistics says what each part means.
    ``LongcatModel``: one integer array ``(expert layers, held experts + 2)``
    (tokens a held expert, picks to zero experts, picks to absent experts)
    for ``observe.note_moe``. ``XingModel``: a dict of that array (``"moe"``)
    and two scalars of its hyper-connected residual (``"hc_residual"``,
    ``"hc_clamped"``) for ``observe.note_hyper_connection``. ``BrumbyModel``:
    a dict with one part, ``"retention"``: three scalars of its states for
    ``observe.note_retention``. ``SdarModel``: ``LongcatModel``'s integer
    array, summed over a block step's passes.

    ``block``: the tokens a step yields a sequence, 1 for a model that
    decodes a token at a time."""

    prefill: Callable
    decode_step: Callable
    prefill_suffix: Optional[Callable] = None
    verify: Optional[Callable] = None
    note_stats: Optional[Callable] = None
    block: int = 1
