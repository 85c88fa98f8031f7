"""Block-diffusion statistics (docs/OBSERVABILITY.md § Block diffusion):
how many forward passes a block step ran and how many tokens it yields. The
pass count is made on the device by a block model's step (``models.sdar``)
and reaches the host in the read the step already makes; the masked
positions and the tokens a block yields the host knows from what it
launched."""

from __future__ import annotations

from deeplearning4j_tpu.observe.registry import default_registry


def note_diffusion(passes: int, masked: int, tokens: int, blocks: int,
                   span=None) -> dict:
    """One block step: ``passes`` forward passes (the last is the store
    pass over the clean blocks), ``masked`` masked positions at its start
    over the active slots, ``tokens`` tokens its blocks yield before a
    commit cuts any, ``blocks`` blocks (the active slots). Counts
    ``dl4j_tpu_diffusion_passes_total{kind=denoise|store}`` and
    ``dl4j_tpu_diffusion_blocks_total`` and sets ``dd_passes``,
    ``dd_masked`` and ``dd_tokens`` on ``span``. Returns those three."""
    passes = int(passes)
    m = default_registry()
    m.counter("dl4j_tpu_diffusion_passes_total", kind="denoise").inc(
        max(passes - 1, 0))
    m.counter("dl4j_tpu_diffusion_passes_total", kind="store").inc(
        min(passes, 1))
    m.counter("dl4j_tpu_diffusion_blocks_total").inc(int(blocks))
    out = {"dd_passes": passes, "dd_masked": int(masked),
           "dd_tokens": int(tokens)}
    if span is not None:
        span.set(**out)
    return out
