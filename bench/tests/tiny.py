"""A CPU-sized configuration and mix for the tests: the program's
granite-moe block at toy widths, served by the same driver."""

MODEL = {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 32, "vocab": 250, "vocab_padded": 256,
    "pattern": ["attn", "moe"], "moe_experts": 8, "moe_experts_padded": 16,
    "moe_top_k": 2, "rope_theta": 10000.0, "norm_eps": 1e-06,
    "activation": "silu", "tie_word_embeddings": True, "dtype": "float32",
}

CONF = {
    "name": "tiny-moe", "driver": "lm_serving",
    "arch": "granite-moe-3b-a800m",
    "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 32, "vocab": 256,
                  "moe_experts": 8, "moe_top_k": 2,
                  "moe_capacity_factor": 8.0},
    "width": 4, "max_len": 128, "model": MODEL,
    "limits": {"token_gap": 0.05},
}

MIX = {
    "generator": "poisson", "rate_rps": 6.0,
    "lead_in_s": 1,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 60},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "check_tokens": 48,
}

SPEC = {
    "end_to_end": [
        {"name": "ttft_p50_ms", "unit": "ms"},
        {"name": "itl_p95_ms", "unit": "ms"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": n, "unit": u} for n, u in [
            ("queue_wait_p90_ms", "ms"), ("prefill_ms", "ms"),
            ("prefill_mfu", "%"), ("decode_ms", "ms"),
            ("decode_roofline", "%"), ("decode_mfu", "%"),
            ("device_idle", "%")]],
}

DENSE_MODEL = dict(MODEL, pattern=["attn", "mlp"], d_ff=128, vocab=256,
                   moe_experts=0, moe_experts_padded=0, moe_top_k=0)

DENSE_CONF = {
    "name": "tiny-dense", "driver": "lm_serving", "arch": "granite-8b",
    "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab": 256},
    "width": 4, "max_len": 128, "model": DENSE_MODEL,
    "limits": {"token_gap": 0.05},
}

# the block varied in the ways the reference takes as configuration keys:
# no positional rotation, a sigmoid-gated shared expert, two layers a group
VARIANT_MODEL = dict(MODEL, n_layers=4, n_groups=2,
                     pattern=["attn", "moe", "attn", "moe"], rope=False,
                     moe_shared_d_ff=48, moe_shared_gate="sigmoid")

VARIANT_CONF = {
    "name": "tiny-variant", "driver": "lm_serving",
    "arch": "granite-moe-3b-a800m",
    "overrides": dict(CONF["overrides"], n_layers=4,
                      pattern=(("attn", "moe"), ("attn", "moe")),
                      use_rope=False, moe_shared_dff=48),
    "width": 4, "max_len": 128, "model": VARIANT_MODEL,
    "limits": {"token_gap": 0.05},
}
