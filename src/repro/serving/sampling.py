"""Samplers for the serving loop: greedy, temperature, top-k, top-p.

Pure-JAX, jittable.  :class:`SamplingParams` + :func:`make_row_sampler` are
the single entry point every serving path routes through: the continuous
:class:`~repro.serving.api.ServeSession` builds one sampler per admitted
request (per-row temperature / top-k / top-p / seed), and the static
:class:`~repro.serving.scheduler.BatchServer` compatibility wrapper rides
the same machinery.  ``device=True`` variants keep the drawn tokens on
device so a tight decode loop (``KVSwapEngine.generate``) never bounces
logits through numpy per token — the only host transfer is the final stack
of generated ids.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (``temperature == 0`` ⇒ greedy).

    One request = one :class:`SamplingParams`; a continuous batch mixes
    greedy and stochastic rows freely because each slot samples its own
    logits row through its own sampler.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0

    @property
    def is_greedy(self) -> bool:
        return self.temperature == 0.0


def make_row_sampler(params: SamplingParams | None = None):
    """The unified sampler factory: ``sampler(logits [n, V]) -> tokens [n]``.

    Greedy for ``None`` / zero temperature (one jitted argmax, no RNG
    state); otherwise a stateful categorical sampler with the requested
    temperature / top-k / top-p and its own auto-splitting key.
    """
    if params is None or params.is_greedy:
        return greedy
    return make_sampler(temperature=params.temperature, top_k=params.top_k,
                        top_p=params.top_p, seed=params.seed)


@jax.jit
def greedy_rows(logits: jax.Array) -> jax.Array:
    """``[B, V] -> [B]`` int32 argmax on the device: every greedy row of a
    decode step's logits block in one program, so the host pulls ``B`` ids
    and not the block.  A row's id is the one it gets alone."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def greedy(logits) -> np.ndarray:
    return np.asarray(greedy_rows(logits))


def greedy_device(logits) -> jax.Array:
    """Jitted argmax returning the device array (no per-token host pull)."""
    return greedy_rows(logits)


def make_sampler(*, temperature: float = 1.0, top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0, device: bool = False):
    """Stateful (auto-splitting) categorical sampler.

    One vectorized ``jax.random.categorical`` draw over the whole batch per
    call.  With ``device=True`` the sampler returns the device array instead
    of pulling to numpy (same draws; callers that index rows should keep the
    default).
    """
    key_holder = {"key": jax.random.PRNGKey(seed)}

    @functools.partial(jax.jit, static_argnames=())
    def _sample(key, logits):
        lg = logits.astype(jnp.float32) / max(temperature, 1e-6)
        if top_k:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if top_p:
            sorted_lg = jnp.sort(lg, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_lg, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # smallest logit still inside the nucleus
            inside = cum - probs < top_p
            cutoff = jnp.min(jnp.where(inside, sorted_lg, jnp.inf), axis=-1,
                             keepdims=True)
            lg = jnp.where(lg < cutoff, -jnp.inf, lg)
        return jax.random.categorical(key, lg, axis=-1)

    def sampler(logits):
        key_holder["key"], sub = jax.random.split(key_holder["key"])
        drawn = _sample(sub, logits)
        return drawn if device else np.asarray(drawn)

    return sampler
