"""Bytes a latent-attention configuration's cache holds and its decode
attention must read, computed from the configuration's sizes (no program
code).  A token leaves ONE row a layer: `kv_lora_rank` compressed values and
`qk_rope_head_dim` roped ones, stored padded to the device's 128 lanes."""

BF16 = 2  # bytes: the dtype every configuration of the benchmark runs in
LANES = 128  # the minor dimension of the device's tiled layout

#: the decode kernel's call name in a trace
DECODE_KERNEL = "latent_attention_decode"


def is_latent(cfg: dict) -> bool:
    return bool(cfg.get("kv_lora_rank"))


def row_values(cfg: dict) -> int:
    """Values of one token's row in one layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def row_bytes_stored(cfg: dict) -> int:
    """One token's row in one layer as the device stores it."""
    return -(-row_values(cfg) // LANES) * LANES * BF16


def token_bytes_stored(cfg: dict) -> int:
    """What `engine_kv_token_bytes` must read: every layer's row."""
    return cfg["num_hidden_layers"] * row_bytes_stored(cfg)


def context_read_bytes(cfg: dict, context_tokens: float) -> float:
    """Bytes decode attention must read for `context_tokens` (the sum over
    decode steps of the live lanes' cached tokens): each token's row, once
    (scores and values come from the same row), in every layer."""
    return context_tokens * token_bytes_stored(cfg)


def kernel_seconds(trace: dict) -> float:
    """Device self-seconds of the decode kernel's calls in a reduced trace."""
    return sum(s for label, s in trace["op_s"].items()
               if label.startswith(DECODE_KERNEL))
