"""Operations a configuration's forward needs, computed from its sizes."""


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections and the output head (the tied embedding serves as
    the head; the embedding lookup itself multiplies nothing)."""
    h = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nq)
    d = cfg.get("head_dim") or h // nq
    f = cfg["intermediate_size"]
    per_layer = h * (nq + 2 * nkv) * d + nq * d * h + 3 * h * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * h


def forward_flops_per_token(cfg: dict) -> int:
    """2 x matmul parameters: the dense part of one token's forward.
    Attention's own score and value products are left out (they grow with
    context and are small at this benchmark's lengths), so a utilisation
    computed from this is a slight under-count, never an over-count."""
    return 2 * matmul_params(cfg)
