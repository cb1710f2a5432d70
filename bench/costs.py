"""Operations and bytes of the model's work, computed from shapes alone.

A matmul of [m, k] by [k, n] is 2*m*k*n operations.  Bytes are the least a
call must move through HBM: every weight it reads once, the K/V context it
attends over, and its activations in and out.
"""

from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    """Weights one dense GQA layer multiplies by: Q, K, V, O and the
    three SwiGLU matrices."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv, f = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["intermediate_size"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f


def layer_params(cfg: dict) -> int:
    """Every weight of a layer: the matmuls plus the norm scales."""
    extra = 2 * cfg["hidden_size"] + (2 * cfg["head_dim"] if cfg["qk_norm"] else 0)
    return layer_matmul_params(cfg) + extra


def attention_flops(cfg: dict, queries: int, keys: int) -> int:
    """Scores and the weighted sum: two [H, hd] x [hd, keys] products per query."""
    return 4 * queries * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def decode_block_flops(cfg: dict, batch: int, context: int) -> int:
    """One call of the jitted decode layer: ``batch`` tokens, each attending
    ``context`` gathered tokens plus itself."""
    return 2 * batch * layer_matmul_params(cfg) + attention_flops(cfg, batch, context + 1)


def decode_block_bytes(cfg: dict, batch: int, context: int, itemsize: int) -> int:
    """Least HBM traffic of that call: the layer's weights, the gathered K
    and V, the hidden state in and out, and the new K and V out."""
    kv_row = cfg["num_key_value_heads"] * cfg["head_dim"]
    weights = layer_params(cfg) * itemsize
    context_kv = 2 * batch * context * kv_row * itemsize
    activations = 2 * batch * cfg["hidden_size"] * itemsize + 2 * batch * kv_row * itemsize
    return weights + context_kv + activations


def decode_token_flops(cfg: dict, attended: int) -> int:
    """Model operations to decode one token attending ``attended`` earlier
    tokens per layer: every layer, then the output head."""
    per_layer = 2 * layer_matmul_params(cfg) + attention_flops(cfg, 1, attended + 1)
    return cfg["num_hidden_layers"] * per_layer + 2 * cfg["hidden_size"] * cfg["vocab_size"]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
