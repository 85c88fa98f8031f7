"""Power-retention state statistics (docs/OBSERVABILITY.md § Retention
state): how near the division by the normaliser is to noise, how large the
state has grown and how long the memory's horizon is. The numbers are made
on the device by ``models.brumby`` and reach the host in the read the serving
step already makes, beside the other models' (``observe.note_moe``,
``observe.note_hyper_connection``)."""

from __future__ import annotations

from deeplearning4j_tpu.observe.registry import default_registry

# the smallest phi(q)^T z of a step: at the normaliser's epsilon (1e-6) the
# division is noise, a healthy layer reads above 1e-2
_DEN_BOUNDS = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0, 1e3)

FORMS = ("state", "quadratic")


def note_retention(den_min, state_absmax, decay_mean, span=None, *,
                   form: str = "state") -> dict:
    """``den_min``: the smallest normaliser ``phi(q)^T z`` over the slots (or
    a prompt's positions), heads and layers of a step; ``state_absmax``: the
    largest ``|S|`` entry the step touched; ``decay_mean``: the mean
    ``exp(g)``; ``form``: which form of the function ran (``"state"``: the
    recurrence of a decode step, ``"quadratic"``: a prefill). Observes
    histogram ``dl4j_tpu_retention_den_min``, sets gauge
    ``dl4j_tpu_retention_state_absmax``, counts
    ``dl4j_tpu_retention_steps_total{form}`` and sets ``ret_den_min``,
    ``ret_state_absmax`` and ``ret_decay_mean`` on ``span``. Returns those
    three."""
    if form not in FORMS:
        raise ValueError(f"form must be one of {FORMS}, got {form!r}")
    out = {"ret_den_min": float(den_min),
           "ret_state_absmax": float(state_absmax),
           "ret_decay_mean": float(decay_mean)}
    m = default_registry()
    m.histogram("dl4j_tpu_retention_den_min",
                bounds=_DEN_BOUNDS).observe(out["ret_den_min"])
    m.gauge("dl4j_tpu_retention_state_absmax").set(out["ret_state_absmax"])
    m.counter("dl4j_tpu_retention_steps_total", form=form).inc()
    if span is not None:
        span.set(**out)
    return out
