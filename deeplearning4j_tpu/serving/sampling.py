"""Per-slot token sampling — temperature / top-k / top-p, jit-stable.

One vectorized function over the whole slot axis: every knob is a device
array of shape ``(S,)`` so heterogeneous requests (a greedy slot next to a
temperature-1.2 top-p slot) share ONE compiled sampler — no per-request
recompiles, which is the entire point of the fixed-capacity decode step.

The sampler does only what the bank's knobs ask for. Inside that one
program a scalar conditional (``lax.switch`` on :func:`sampler_path`, read
from the knobs on the device) runs ONE of three bodies (``SAMPLER_PATHS``):

* ``greedy`` — no slot has ``temperature > 0``: the argmax, and nothing
  else (no divide, no soft-max, no sort, no key split, no draw);
* ``plain`` — some slot samples and no sampling slot has ``top_k > 0`` or
  ``top_p < 1``: per-slot keys and a categorical draw, no sort;
* ``filter`` — some sampling slot asks for top-k or top-p: two full sorts
  of the ``(S, V)`` scores (3.7 of a 7.9 ms decode program at 32 x 50257 on
  a TPU v5e, PERF.md PR 28, which is why the other two bodies exist).

A slot's token depends on its own logits, its own key and its own knobs
only, never on which body the rest of the bank forced: a greedy slot gets
its argmax in every body, and a ``top_k = 0, top_p = 1`` slot draws the same
token from ``plain`` and from ``filter`` (a top-k of the whole vocabulary
keeps every score, and a row whose ``top_p >= 1`` takes no nucleus cut).

PRNG hygiene (graftlint GL004): the caller passes ONE fresh step key; it is
split into per-slot keys HERE, once, and every key is consumed exactly once
by its slot's categorical draw (the greedy body consumes none of it). The
serving engine derives the step key by splitting its root key every
iteration — ``tests/test_serving.py`` asserts no key value ever repeats
across the scheduler loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the sampler's bodies, by :func:`sampler_path`'s index
SAMPLER_PATHS = ("greedy", "plain", "filter")


def sampler_path(temperature, top_k, top_p):
    """Which of ``SAMPLER_PATHS`` a bank's knobs ask for, as an int32 index.

    One predicate for both readers: the device (traced ``(S,)`` arrays, the
    ``lax.switch`` of :func:`sample_tokens`) and the host (the numpy arrays
    the engine uploads, for its counter and span)."""
    sampling = temperature > 0.0
    filtering = sampling & ((top_k > 0) | (top_p < 1.0))
    return (sampling.any().astype(np.int32)
            + filtering.any().astype(np.int32))


def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _scaled(logits, temperature):
    return logits / jnp.maximum(temperature, 1e-6)[:, None]


def _draw(logits, scores, step_key, temperature):
    """A categorical draw over its row of ``scores`` for every sampling
    slot, each with its own split of the key; the argmax of its logits for
    a greedy one."""
    keys = jax.random.split(step_key, scores.shape[0])
    sampled = jax.vmap(jax.random.categorical)(keys, scores).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, _argmax(logits), sampled)


def _greedy(logits, step_key, temperature, top_k, top_p):
    return _argmax(logits)


def _plain(logits, step_key, temperature, top_k, top_p):
    return _draw(logits, _scaled(logits, temperature), step_key, temperature)


def _filter(logits, step_key, temperature, top_k, top_p):
    vocab = logits.shape[-1]
    scaled = _scaled(logits, temperature)

    # top-k: keep scores >= the k-th largest per row (k=0 -> keep all)
    k = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)
    masked = jnp.where(scaled >= kth, scaled, -jnp.inf)

    # top-p (nucleus) on the k-masked distribution: keep the smallest
    # prefix of descending probs whose mass reaches top_p. A sorted token
    # is kept when the mass BEFORE it is < top_p, so the cutoff prob is
    # the smallest kept prob; >= maps the cutoff back to vocab order. A
    # row with top_p >= 1 takes no cut: its float32 cumulative sum can
    # round past 1 before the tail and would drop it
    probs = jax.nn.softmax(masked, axis=-1)
    sp = jnp.sort(probs, axis=-1)[:, ::-1]
    cum = jnp.cumsum(sp, axis=-1)
    keep_sorted = ((cum - sp) < top_p[:, None]) | (top_p >= 1.0)[:, None]
    cutoff = jnp.min(jnp.where(keep_sorted, sp, jnp.inf), axis=-1,
                     keepdims=True)
    masked = jnp.where(probs >= cutoff, masked, -jnp.inf)
    return _draw(logits, masked, step_key, temperature)


def sample_tokens(logits, step_key, temperature, top_k, top_p):
    """Sample one token per slot.

    logits: (S, V) f32; step_key: ONE jax PRNG key for this decode step;
    temperature: (S,) f32 — ``<= 0`` means greedy argmax for that slot;
    top_k: (S,) int32 — ``0`` disables the k cutoff;
    top_p: (S,) f32 — ``1.0`` disables the nucleus cutoff.
    Returns (S,) int32.
    """
    return jax.lax.switch(
        sampler_path(temperature, top_k, top_p), (_greedy, _plain, _filter),
        logits.astype(jnp.float32), step_key, temperature, top_k, top_p)
