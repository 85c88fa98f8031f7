"""What the serving engine asks a model for (docs/SERVING.md § The model
protocol). ``serving.GenerativeEngine`` knows no model by name: any handle
with ``cfg`` (``vocab_size``, ``eos_token``, ``max_position``), ``params``,
``cache_rows()`` and ``serving_programs()`` is served through the same
scheduler, paged cache and sampler."""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional


class CacheRows(NamedTuple):
    """The geometry of what a token leaves in the paged cache: ``layers``
    attention (sub-)layers, each with ``sides`` rows a token (2: a key row
    and a value row; 1: one latent row) of ``width`` values. The pool is
    ``(layers, sides, pages + 1, page_size, width)``."""

    layers: int
    sides: int
    width: int


class ServingPrograms(NamedTuple):
    """A model's jittable programs, bound to its configuration.

    * ``prefill(params, ids (1, T), prompt_len)`` -> ``(logits (1, V) of the
      last real position, rows (layers, sides, T, width), stats)``.
    * ``decode_step(params, kv_pages, tokens, positions, page_table,
      seq_lens_incl, write_page, write_offset)`` -> ``(kv_pages, logits
      (S, V), stats)``: one token for every slot against the donated pool.
    * ``prefill_suffix(params, ids, prefix_kv, prefix_len, suffix_len)`` ->
      ``(logits (1, B, V), rows (layers, sides, B, width))``, optional: the
      radix prefix cache needs it.
    * ``verify(params, kv_pages, tokens, seq_lens, page_table, write_pages,
      write_offsets, page_size=)`` -> ``(kv_pages, greedy (S, B))``,
      optional: speculative decoding needs it.

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
    ``"hc_clamped"``) for ``observe.note_hyper_connection``."""

    prefill: Callable
    decode_step: Callable
    prefill_suffix: Optional[Callable] = None
    verify: Optional[Callable] = None
    note_stats: Optional[Callable] = None
