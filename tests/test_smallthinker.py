"""SmallThinker's served path against its plain float32 reference.

One published period ``[window, window, window, full]`` at smoke widths:
4 experts of which 2 are active, routed before attention, and a 16-token
window, so contexts of 48-80 tokens wrap every ring several times.  The
reference is ``bench/reference/smallthinker.py``, loaded by path; it
imports nothing of the program.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import PrefixCache, PrefixCacheConfig
from repro.configs import smallthinker_21b_a3b as st
from repro.core.engine import EngineConfig
from repro.core.lowrank import fit_adapter
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving.api import ServeSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 16
# Both sides compute in float32 and differ only in the order of their sums
# (chunked prefill, the engine's gathered context, grouped against dense
# expert matmuls): at most 4e-6 on logits of RMS about 1.  A side that
# rounded to bfloat16 (2^-8 relative) would be off by about 1e-2.
ATOL = 5e-5


def _reference():
    path = os.path.join(ROOT, "bench", "reference", "smallthinker.py")
    spec = importlib.util.spec_from_file_location("smallthinker_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(st.smoke_config(), n_layers=4, block_pattern=("moe_attn",) * 4,
                               sliding_window=W, window_layers=(0, 1, 2), nope_layers=(3,))


def _ref_cfg(cfg):
    """The reference's view: published layers 1-4 of the [0,1,1,1] layout."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": 1e-6, "rope_theta": cfg.rope_theta,
            "sliding_window_size": cfg.sliding_window,
            "sliding_window_layout": [0, 1, 1, 1, 0], "rope_layout": [0, 1, 1, 1, 0],
            "published_layers": [1, 2, 3, 4],
            "moe_num_active_primary_experts": cfg.moe_top_k,
            "tie_word_embeddings": cfg.tie_embeddings}


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(jax.random.PRNGKey(0), cfg)


def _ref_logits(params, cfg, tokens, positions):
    rc = _ref_cfg(cfg)
    x = REF.hidden(params, rc, np.asarray(tokens))
    with jax.default_matmul_precision("highest"):
        return np.asarray(x[np.asarray(positions)] @ params["lm_head"])


def _session(cfg, params, cache, slots=2):
    model = T.TransformerAdapter(cfg)
    calib = np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 64))
    x = model.embed(params, jnp.asarray(calib))
    pos = jnp.arange(64)[None]
    for i in range(3):
        x = model.prefill_block(params, i, x, pos)[0]
    _, k, _ = model.prefill_block(params, 3, x, pos)
    # M·G = 96 covers every token of these contexts: attention is exact
    ecfg = EngineConfig(group_size=4, n_select=24, rank=8, reuse_capacity=32, max_seq=128)
    return ServeSession(model, params, ecfg, slots=slots,
                        adapter=fit_adapter(np.asarray(k), rank=8), prefix_cache=cache)


class _Logits:
    """Every logits row the engine hands back, by admission: the prompt
    and ``(position, logits)`` of each token chosen from them."""

    def __init__(self, engine):
        self.records, self._slot = [], {}
        admit, step = engine.admit_row, engine.decode_step

        def admit_row(bi, tokens, cache=None):
            out = admit(bi, tokens, cache)
            rec = (np.asarray(tokens), [(len(tokens) - 1, np.asarray(out))])
            self.records.append(rec)
            self._slot[bi] = rec
            return out

        def decode_step(token_ids):
            pos = engine.row_seq.copy()
            active = engine.row_active.copy()
            out = np.asarray(step(token_ids))
            for bi in np.flatnonzero(active):
                self._slot[bi][1].append((int(pos[bi]), out[bi]))
            return out

        engine.admit_row, engine.decode_step = admit_row, decode_step

    def check(self, params, cfg, completed):
        """Each request's logits against the reference's full forward over
        its prompt and served tokens."""
        outs = {r.prompt.tobytes(): r.output for r in completed.values()}
        for prompt, rows in self.records:
            seq = np.concatenate([prompt, outs[prompt.tobytes()][:-1]])
            pos = [p for p, _ in rows]
            ref = _ref_logits(params, cfg, seq, pos)
            np.testing.assert_allclose(np.stack([lg for _, lg in rows]), ref,
                                       rtol=0, atol=ATOL)


def _turns(sess, contexts, turn, max_new):
    """Each context built in ``turn``-token turns through the prefix cache
    (one token each), then decoded for ``max_new`` tokens."""
    for end in range(turn, max(len(c) for c in contexts), turn):
        for c in contexts:
            if end <= len(c) - turn:
                sess.submit(c[:end], 1)
        sess.drain()
    for c in contexts:
        sess.submit(c, max_new)
    sess.drain()


def test_served_prefill_and_decode_match_the_reference(cfg, params):
    """Contexts of 48-80 tokens, built in 16-token turns: every restore
    crosses the window (the rings are seeded from the restored blocks of
    the last 16 tokens and the new turn), and decode wraps every ring."""
    rng = np.random.default_rng(7)
    contexts = [rng.integers(0, cfg.vocab_size, n) for n in (48, 64, 80)]
    cache = PrefixCache(PrefixCacheConfig(block_tokens=8, budget_bytes=8 << 20))
    sess = _session(cfg, params, cache)
    eng = sess.engine
    # only the full layer is a KVSwap layer
    assert eng.kv_layers == [3] and eng.window_layers == [0, 1, 2]
    assert eng.store.n_layers == 1 and len(eng.k_lr) == len(eng.reuse) == 1
    seen = _Logits(eng)
    _turns(sess, contexts, 16, 12)
    # each request restored every turn before it
    assert all(r.cached_tokens == 16 * ((len(r.prompt) - 1) // 16)
               for r in sess.completed.values())
    seen.check(params, cfg, sess.completed)
    sess.close()
    cache.close()


def test_restore_falls_back_where_no_windowed_block_covers_the_window(cfg, params):
    """A chain published after decoding past its prompt holds the windowed
    layers' K/V only for its last 16 tokens; a later prompt that matches it
    at 56 tokens restores the 32 an earlier turn covered, and agrees."""
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 72)
    cache = PrefixCache(PrefixCacheConfig(block_tokens=8, budget_bytes=8 << 20))
    sess = _session(cfg, params, cache, slots=1)
    seen = _Logits(sess.engine)
    sess.submit(base[:32], 1)      # the rings hold all 32: blocks 16-31 windowed
    sess.drain()
    # restores 32; after 9 decode steps the rings hold positions 41-56, so
    # of the 56 tokens it publishes only block 48-55 carries windowed K/V
    rid = sess.submit(base[:48], 10)
    sess.drain()
    assert sess.completed[rid].cached_tokens == 32
    # the next prompt continues what was served
    served = np.concatenate([base[:48], sess.completed[rid].output])[:56]
    nxt = np.concatenate([served, base[56:72]])
    assert cache.peek(nxt) == 56
    rid = sess.submit(nxt, 4)
    sess.drain()
    assert sess.completed[rid].cached_tokens == 32
    seen.check(params, cfg, sess.completed)
    sess.close()
    cache.close()


def _forced_router(params, expert):
    """Every token of every layer routed first to ``expert``: the embedding
    gains a large constant feature, which each router maps to it."""
    p = jax.tree.map(lambda a: a, params)
    p["embed"] = p["embed"].at[:, 0].add(10.0)
    for blk in p["blocks"]:
        blk["moe"]["router"] = blk["moe"]["router"].at[0, expert].set(5.0)
    return p


def test_dropless_experts_take_every_token_routed_to_one(cfg, params):
    """With all 64 tokens on one expert, the capacity-bounded layer keeps
    40 of them (1.25 × 64 · 2 / 4); the dropless one computes all, as the
    reference does."""
    p = _forced_router(params, expert=2)
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, 64)
    h = L.rmsnorm(p["blocks"][0]["attn_norm"], p["embed"][jnp.asarray(tokens)])
    top = jax.lax.top_k(h @ p["blocks"][0]["moe"]["router"], 1)[1]
    assert (np.asarray(top) == 2).all()
    logits, _ = T.forward(p, cfg, jnp.asarray(tokens[None]))
    ref = _ref_logits(p, cfg, tokens, np.arange(64))
    np.testing.assert_allclose(np.asarray(logits[0]), ref, rtol=0, atol=ATOL)


def test_nope_layer_predicts_an_unrotated_query(cfg, params):
    """The full layer's predicted query is ``RMSNorm(x) Wq`` at every
    position; a windowed layer's turns with it."""
    model = T.TransformerAdapter(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.d_model))
    at = [model.predict_query(params, layer, x, jnp.asarray([0, 37]))
          for layer in (3, 2)]
    again = [model.predict_query(params, layer, x, jnp.asarray([9, 100]))
             for layer in (3, 2)]
    h = L.rmsnorm(params["blocks"][3]["attn_norm"], x)
    plain = (h @ params["blocks"][3]["attn"]["wq"]).reshape(2, cfg.n_heads, cfg.head_dim)
    np.testing.assert_allclose(np.asarray(at[0]), np.asarray(plain), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(at[0]), np.asarray(again[0]))
    assert not np.allclose(np.asarray(at[1]), np.asarray(again[1]))
