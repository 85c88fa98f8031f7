"""Hyper-connected residual statistics (docs/OBSERVABILITY.md § Residual
path): how far the Sinkhorn-normalised stream-mixing matrices of a step are
from doubly stochastic, and how many of their logits met the clamp. The
numbers are made on the device by ``models.xing.hyper_maps`` and reach the
host in the read the serving step already makes, beside the expert layer's
(``observe.note_moe``)."""

from __future__ import annotations

from deeplearning4j_tpu.observe.registry import default_registry

# the largest |row sum - 1| or |column sum - 1| of a step: 1e-7 is float32
# rounding, 1 is no normalisation at all
_RESIDUAL_BOUNDS = (1e-7, 3e-7, 1e-6, 3e-6, 1e-5, 3e-5, 1e-4, 3e-4, 1e-3,
                    1e-2, 1e-1, 1.0)


def note_hyper_connection(residual, clamped, span=None) -> dict:
    """``residual``: the largest ``|row sum - 1|`` or ``|column sum - 1|`` of
    any ``H_res`` of the step after its Sinkhorn iterations; ``clamped``: how
    many entries of ``R`` met ``mhc_h_res_clamp_min/max`` before the ``exp``.
    Observes histogram ``dl4j_tpu_hc_sinkhorn_residual``, counts
    ``dl4j_tpu_hc_clamped_total`` and sets ``hc_residual``/``hc_clamped`` on
    ``span``. Returns those two."""
    out = {"hc_residual": float(residual), "hc_clamped": int(clamped)}
    m = default_registry()
    m.histogram("dl4j_tpu_hc_sinkhorn_residual",
                bounds=_RESIDUAL_BOUNDS).observe(out["hc_residual"])
    m.counter("dl4j_tpu_hc_clamped_total").inc(out["hc_clamped"])
    if span is not None:
        span.set(**out)
    return out
