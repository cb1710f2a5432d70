"""End-to-end quality: teacher-forced perplexity through the real engine.

The policy-level quality bench (quality_niah) isolates selection fidelity;
this one closes the loop: a small LM trained on the synthetic stream is
evaluated teacher-forced, with every attention step served by the full
KVSwap runtime (disk store + prediction + reuse + rolling buffers), across
selection budgets — the Fig. 13b accuracy axis measured as perplexity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit
from repro.core.engine import EngineConfig, KVSwapEngine
from repro.data import SyntheticLMStream
from repro.models.transformer import (ModelConfig, TransformerAdapter, forward,
                                      init_params)
from repro.training.optim import AdamWConfig, adamw_init
from repro.training.train import TrainState, make_train_step


def train_model(steps=120):
    cfg = ModelConfig(name="ppl", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=97)
    params = init_params(jax.random.PRNGKey(0), cfg)
    stream = SyntheticLMStream(cfg.vocab_size, seed=21)
    step = make_train_step(forward, cfg, AdamWConfig(lr=3e-3), total_steps=steps)
    state = TrainState(params, adamw_init(params))
    for i in range(steps):
        b = stream.batch(i, 8, 32)
        state, _ = step(state, {k: jnp.asarray(v) for k, v in b.items()})
    return cfg, state.params, stream


def engine_xent(cfg, params, tokens, *, n_select, rank) -> float:
    """Teacher-forced token cross-entropy with attention served by KVSwap."""
    adapter = TransformerAdapter(cfg)
    b, s = tokens.shape
    prefix = 16
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((256, cfg.n_kv_heads, cfg.head_dim))
    ecfg = EngineConfig(group_size=4, n_select=n_select, rank=rank,
                        reuse_capacity=2 * n_select, max_seq=s + 8,
                        predict_from="prev")
    lls = []
    with KVSwapEngine(adapter, params, ecfg, batch=b, calib_k=calib) as eng:
        logits = eng.prefill(tokens[:, :prefix])
        for t in range(prefix, s):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            lls.append(np.asarray(jnp.take_along_axis(
                logp, jnp.asarray(tokens[:, t:t + 1]), -1))[:, 0])
            logits = eng.decode_step(tokens[:, t])
    return float(-np.mean(lls))


def full_xent(cfg, params, tokens, prefix=16) -> float:
    logits, _ = forward(params, cfg, jnp.asarray(tokens))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ll = jnp.take_along_axis(logp[:, prefix - 1:-1], jnp.asarray(tokens[:, prefix:])[..., None], -1)
    return float(-ll.mean())


def main() -> str:
    with Timer() as t:
        cfg, params, stream = train_model()
        tokens = stream.batch(99_999, 4, 64)["tokens"]
        base = full_xent(cfg, params, tokens)
        print("budget,xent,ppl,delta_vs_full")
        print(f"full,{base:.4f},{np.exp(base):.2f},0.000")
        results = {}
        for n_sel, tag in ((16, "budget=64tok"), (8, "budget=32tok"), (4, "budget=16tok")):
            x = engine_xent(cfg, params, tokens, n_select=n_sel, rank=16)
            results[tag] = x - base
            print(f"{tag},{x:.4f},{np.exp(x):.2f},{x - base:+.4f}")
    emit("e2e_perplexity", t.us,
         " ".join(f"{k}:+{v:.3f}" for k, v in results.items()))
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
