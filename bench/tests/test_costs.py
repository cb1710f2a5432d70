from bench import costs, spec


def test_decode_block_counts_at_qwen3_8b_widths():
    cfg = spec.config("qwen3-8b")
    # d 4096, 32 query and 8 KV heads of 128, d_ff 12288
    matmul = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12288
    assert costs.layer_matmul_params(cfg) == matmul == 192_937_984
    assert costs.layer_params(cfg) == matmul + 2 * 4096 + 2 * 128
    b, ctx = 8, 404
    flops = 2 * b * matmul + 4 * b * 32 * 128 * (ctx + 1)
    assert costs.decode_block_flops(cfg, b, ctx) == flops
    kv = 2 * b * ctx * 8 * 128 * 4
    act = 2 * b * 4096 * 4 + 2 * b * 1024 * 4
    assert costs.decode_block_bytes(cfg, b, ctx, 4) == costs.layer_params(cfg) * 4 + kv + act


def test_decode_token_flops_adds_the_head():
    cfg = spec.config("qwen3-8b")
    per_layer = 2 * costs.layer_matmul_params(cfg) + 4 * 32 * 128 * 401
    assert costs.decode_token_flops(cfg, 400) == 4 * per_layer + 2 * 4096 * 151936


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_seconds(100.0, 50.0, peak) == (5.0, "memory")
    assert costs.roofline_seconds(1000.0, 5.0, peak) == (10.0, "compute")
