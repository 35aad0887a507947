"""Token sampling for the compiled decode loop.

One traced program must serve every request mix, so the greedy/
temperature switch is DATA, not structure: ``temperature`` is a
per-slot traced vector and slots with ``temperature <= 0`` take the
argmax while the rest draw from the (optionally top-k-truncated)
softmax — a `where` between two always-computed candidates, the usual
price of branchless batching. ``top_k`` stays a static int (it
changes the lowering via `lax.top_k`), read once per engine from
``ZOO_TPU_GEN_TOP_K`` so the serving step still compiles exactly
once.

Speculative decoding (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding") reuses the same distribution:
:func:`sampling_probs` exposes the EXACT per-slot distribution
:func:`sample_tokens` draws from (a one-hot at the argmax for greedy
slots), and :func:`speculative_accept` runs the rejection-sampling
acceptance test — accept draft ``d_i`` with probability
``min(1, p_i(d_i) / q_i(d_i))``, and on the first rejection resample
from the residual ``norm(max(p - q, 0))``. The emitted stream is
distributed EXACTLY as target-only sampling; for greedy slots the
one-hot ``p`` collapses the test to ``d_i == argmax p_i`` and the
residual to the argmax itself, so greedy speculation is byte-exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("zoo:decode/sampling")
def sample_tokens(rng, logits, temperature, top_k: int = 0):
    """Next-token ids for a batch of slots.

    logits: (S, V); temperature: scalar or (S,) — ``<= 0`` means
    greedy for that slot; ``top_k``: static, 0/negative disables
    truncation. Returns (S,) int32.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), logits.shape[:1])
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    if top_k and top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled >= kth, scaled, -1e30)
    sampled = jax.random.categorical(rng, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def sampling_probs(logits, temperature, top_k: int = 0):
    """The per-slot distribution :func:`sample_tokens` draws from,
    as explicit probabilities: greedy slots (``temperature <= 0``)
    get a one-hot at the argmax, the rest the top-k-truncated
    temperature softmax. logits: (…, S, V) → (…, S, V) f32.

    This is what speculative verification scores drafts against — it
    must match `sample_tokens` exactly (same truncation, same
    greedy/temperature switch) or acceptance is biased.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), logits.shape[:-1])
    scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]
    if top_k and top_k > 0 and top_k < v:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled >= kth, scaled, -1e30)
    probs = jax.nn.softmax(scaled, axis=-1)
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), v,
                            dtype=jnp.float32)
    return jnp.where((temperature > 0.0)[..., None], probs, greedy)


def speculative_accept(rng, p, q, drafts):
    """Rejection-sampling acceptance for one speculative round.

    p / q: (S, K, V) f32 — the target / drafter sampling
    distributions at each of the K draft positions (both from
    :func:`sampling_probs`, so greedy slots carry one-hots); drafts:
    (S, K) int32 proposed ids. Returns ``(n_accept, corrected)``:

    - ``n_accept`` (S,) int32 — length of the accepted draft prefix
      (position i accepted iff ``u_i < p_i(d_i) / q_i(d_i)``, all
      earlier positions accepted);
    - ``corrected`` (S,) int32 — a token drawn from the residual
      ``norm(max(p - q, 0))`` at the first rejected position
      (meaningful only when ``n_accept < K``; whenever a rejection
      occurred the residual has positive mass, since rejection
      implies ``p(d) < q(d)`` there).

    Greedy falls out with no special case: ``p`` one-hot means the
    ratio is ``1/q >= 1`` (always accept) at the argmax and ``0``
    elsewhere, and the residual is a delta at the argmax.
    """
    k = drafts.shape[1]
    p_d = jnp.take_along_axis(p, drafts[..., None], axis=-1)[..., 0]
    q_d = jnp.take_along_axis(q, drafts[..., None], axis=-1)[..., 0]
    r_accept, r_fix = jax.random.split(rng)
    u = jax.random.uniform(r_accept, drafts.shape, jnp.float32)
    # u < p/q without the division (q_d can be 0 for greedy drafters)
    accept = u * q_d < p_d
    good = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    n_accept = jnp.sum(good, axis=1).astype(jnp.int32)
    idx = jnp.minimum(n_accept, k - 1)[:, None, None]
    p_r = jnp.take_along_axis(p, idx, axis=1)[:, 0]
    q_r = jnp.take_along_axis(q, idx, axis=1)[:, 0]
    residual = jnp.maximum(p_r - q_r, 0.0)
    corrected = jax.random.categorical(
        r_fix, jnp.log(residual + 1e-30)).astype(jnp.int32)
    return n_accept, corrected
